"""Data pipeline, checkpointing, and supervisor (fault tolerance)."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import all_steps, latest_step, restore, save
from repro.data import SyntheticLM
from repro.launch.supervisor import (
    InjectedFailure,
    StragglerWatchdog,
    SupervisorConfig,
    run_supervised,
)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_data_determinism_and_resume():
    cfg = configs.get_smoke("llama3-8b")
    d = SyntheticLM(cfg, batch=4, seq=32, seed=9)
    a, b = d.batch_at(5), d.batch_at(5)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    # iterate(start_step=k) is identical to skipping k batches: restart-safe
    it = d.iterate(start_step=3)
    np.testing.assert_array_equal(next(it)["inputs"], d.batch_at(3)["inputs"])


def test_data_packing_properties():
    cfg = configs.get_smoke("llama3-8b")
    d = SyntheticLM(cfg, batch=3, seq=64, seed=1, mean_doc_len=16)
    b = d.batch_at(0)
    assert b["inputs"].shape == (3, 64) and b["labels"].shape == (3, 64)
    assert b["inputs"].min() >= 0 and b["inputs"].max() < cfg.vocab
    # doc separators exist and loss mask blanks the positions before them
    assert (b["mask"] == 0).sum() > 0
    np.testing.assert_array_equal(b["inputs"][:, 1:], b["labels"][:, :-1])


def test_data_family_keys():
    for arch in ["whisper-base", "pixtral-12b"]:
        cfg = configs.get_smoke(arch)
        b = SyntheticLM(cfg, batch=2, seq=16).batch_at(0)
        if cfg.family in ("encdec", "audio"):
            assert b["frames"].shape == (2, cfg.enc_seq, cfg.enc_d_model)
        else:
            assert b["prefix_embeds"].shape == (2, cfg.n_patches, cfg.d_model)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.asarray(7)}
    save(tmp_path, 12, tree, metadata={"note": "x"})
    out, meta = restore(tmp_path, 12, jax.eval_shape(lambda: tree))
    assert meta["note"] == "x"
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_keep_k_and_latest(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in [1, 2, 3, 4, 5]:
        save(tmp_path, s, tree, keep=2)
    assert all_steps(tmp_path) == [4, 5]
    assert latest_step(tmp_path) == 5


def test_checkpoint_atomicity(tmp_path):
    """A stale .tmp dir (simulated crash) is ignored by discovery."""
    tree = {"x": jnp.zeros((2,))}
    save(tmp_path, 1, tree)
    (tmp_path / "step_000000002.tmp").mkdir()
    assert latest_step(tmp_path) == 1


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def _mini_loop(tmp_path, inject_at=None, total=20):
    calls = {"init": 0}

    def init_state():
        calls["init"] += 1
        return {"x": jnp.zeros(()), "hist": []}

    def step_fn(state, step):
        return {"x": state["x"] + 1, "hist": state["hist"] + [step]}, {}

    def save_state(d, step, state):
        save(d, step, {"x": state["x"]}, metadata={"hist_len": step})

    def restore_state(d, step):
        out, _ = restore(d, step, {"x": jnp.zeros(())})
        return {"x": out["x"], "hist": []}

    cfg = SupervisorConfig(total_steps=total, ckpt_dir=str(tmp_path),
                           ckpt_every=5, inject_failure_at=inject_at,
                           max_restarts=2)
    state, report = run_supervised(cfg, init_state=init_state,
                                   step_fn=step_fn, save_state=save_state,
                                   restore_state=restore_state)
    return state, report, calls


def test_supervisor_clean_run(tmp_path):
    state, report, calls = _mini_loop(tmp_path)
    # stragglers not asserted: microsecond-scale steps make the watchdog
    # sensitive to host jitter (GC pauses) on a loaded CI machine
    assert report["restarts"] == 0 and report["completed"]
    assert float(state["x"]) == 20


def test_supervisor_restarts_from_checkpoint(tmp_path):
    state, report, calls = _mini_loop(tmp_path, inject_at=13)
    assert report["restarts"] == 1 and report["completed"]
    # resumed from step 9 checkpoint (x == 10), replayed 10..19
    assert float(state["x"]) == 20


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    def init_state():
        return {}

    def step_fn(state, step):
        raise RuntimeError("always fails")

    cfg = SupervisorConfig(total_steps=5, ckpt_dir=str(tmp_path),
                           max_restarts=1)
    with pytest.raises(RuntimeError, match="max_restarts"):
        run_supervised(cfg, init_state=init_state, step_fn=step_fn,
                       save_state=lambda *a: None,
                       restore_state=lambda *a: {})


def test_straggler_watchdog():
    w = StragglerWatchdog(factor=3.0)
    for i in range(16):
        w.observe(i, 0.01)
    w.observe(16, 0.5)       # 50× median
    w.observe(17, 0.011)
    assert w.straggler_steps == [16]


def test_compile_cache_honours_env_else_fixed_checkout_dir(monkeypatch):
    """The one compile-cache setting: JAX_COMPILATION_CACHE_DIR wins and
    nothing is set in code; otherwise a fixed, git-ignored directory of
    the checkout (the same path on every call and in every process)."""
    from repro.launch import compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_dirs.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert set_dirs == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache() \
        == str(root / "artifacts" / "jax_cache")
    assert set_dirs == [("jax_compilation_cache_dir", first)] * 2
