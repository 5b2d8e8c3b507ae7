"""The plain whisper reference follows the program's model: at the smoke
size in float32 on the CPU, the losses of the trainer's first steps agree
with the reference's to rounding (the train cell is out of BENCHMARK.json
until the program's natural gradient is repaired; see PERF.md)."""
import tiny


def test_reference_losses_match_the_trainer():
    R = tiny.harness()
    spec = tiny.spec()
    spec["configs"].append({"name": "whisper-smoke",
                            "file": "bench/tests/whisper-smoke.json"})
    spec["workloads"].append({
        "name": "whisper-smoke.ngd-train", "config": "whisper-smoke",
        "traffic": "ngd-train",
        "traffic_file": "bench/tests/ngd-train-tiny.json", "chips": 1})
    ctx = R.Ctx(spec, "whisper-smoke.ngd-train", 2**31 + 3, 0.2, False)
    R.check_device(ctx, require_tpu=False)
    drv = R.load_module(R.BENCH / "drivers" / "train.py", "bench_train")
    cell = drv.setup(ctx)
    cell.release()
    gaps = drv.gaps(cell.program_readings(), cell.reference())
    assert gaps["loss_gap"] < 1e-5, gaps
