"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program under test (at a tiny size, on the CPU) and drives the rest of a
run of the real cell, whose limits apply unchanged."""
import jax

import tiny

SEED = 2**31 + 1234


def test_sound_runs_are_correct():
    for w in sorted(w["name"] for w in tiny.spec()["workloads"]):
        res = tiny.run(w, SEED)
        assert res["correct"], (w, res["checks"])


def test_solve_answer_altered(monkeypatch):
    import repro.core as core
    orig = core.chol_solve
    monkeypatch.setattr(core, "chol_solve",
                        lambda *a, **k: orig(*a, **k) * 1.001)
    res = tiny.run("table1-n2048.solve", SEED)
    assert not res["correct"], res["checks"]


def test_served_answer_altered(monkeypatch):
    import repro.dist.server as ds
    orig = ds._coalesced_solve

    def altered(*a, **k):
        x, r = orig(*a, **k)
        return x * 1.001, r

    monkeypatch.setattr(ds, "_coalesced_solve", altered)
    res = tiny.run("table1-n2048.serve-adapt", SEED)
    assert not res["correct"], res["checks"]


def test_served_fold_dropped(monkeypatch):
    from repro.serve import OnlineAdaptation
    monkeypatch.setattr(OnlineAdaptation, "fold",
                        lambda self, state, rows, **k: state)
    res = tiny.run("table1-n2048.serve-adapt", SEED)
    assert not res["correct"], res["checks"]


def test_sharded_exchange_left_out(monkeypatch):
    # every cross-chip sum in the sharded request path and refresh is
    # replaced by the local slab's own contribution
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **k: x)
    res = tiny.run("whisper-base.window-sharded", SEED)
    assert not res["correct"], res["checks"]


def test_sharded_answer_altered(monkeypatch):
    import repro.dist.server as ds
    orig = ds.make_sharded_coalesced_solve

    def altered(*a, **k):
        fn = orig(*a, **k)
        return lambda *b: (lambda xr: (xr[0] * 1.001, xr[1]))(fn(*b))

    monkeypatch.setattr(ds, "make_sharded_coalesced_solve", altered)
    res = tiny.run("whisper-base.window-sharded", SEED)
    assert not res["correct"], res["checks"]
