"""``python -m repro.fleet`` — run one fleet worker process.

(The dispatcher spawns these; see ``repro.fleet.launch_fleet``. A
dedicated ``__main__`` avoids runpy re-executing ``worker`` after the
package import already loaded it.)
"""
from repro.fleet.worker import main
from repro.launch.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    main()
