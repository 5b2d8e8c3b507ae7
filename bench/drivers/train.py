"""Driver: the NGD trainer's compiled step, closed loop.

The system under test is ``repro.launch.trainer.build_trainer``'s jitted
step (per-sample scores, the damped Algorithm-1 solve, momentum, the
update) at the configuration's sizes. Set-up builds it once, makes the
weights on the device from the seed, and drives that same compiled step
through the traffic's first ``check_steps`` steps on batches that all
differ; the program's readings of those steps are kept. The window goes
on with the same step and state, one batch per step, made on the device
from the seed, reading each step's loss as a trainer does. After the
window the plain reference repeats the first steps and the readings are
compared leaf by leaf.

Traffic keys: ``batch`` (n samples per step), ``seq`` (decoder tokens per
sample), ``lr``, ``damping``, ``momentum``, ``solver``, ``curvature``,
``total_steps`` (the warm-up/cosine schedule's length), ``check_steps``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

MASK_FRAC = 1e-3      # leaves whose reference gradient is under this
                      # share of the median leaf's are not compared


def _program_config(ctx):
    from repro import configs
    c = ctx.config
    get = configs.get_smoke if c.get("program_preset") == "smoke" \
        else configs.get_config
    cfg = dataclasses.replace(get(c["program_arch"]), dtype=c["dtype"])
    stated = {"d_model": cfg.d_model, "encoder_layers": cfg.enc_layers,
              "decoder_layers": cfg.n_layers,
              "encoder_attention_heads": cfg.enc_n_heads,
              "decoder_attention_heads": cfg.n_heads,
              "encoder_ffn_dim": cfg.enc_d_ff, "decoder_ffn_dim": cfg.d_ff,
              "vocab_size": cfg.vocab,
              "max_source_positions": cfg.enc_seq,
              "max_target_positions": cfg.max_target_positions,
              "layer_norm_eps": cfg.norm_eps}
    for k, v in stated.items():
        if c[k] != v:
            raise ValueError(f"program config {c['program_arch']}: {k}={v}, "
                             f"the configuration file states {c[k]}")
    if cfg.norm_type != "layer" or cfg.mlp_type != "gelu" \
            or not cfg.tie_embeddings or cfg.enc_d_model != cfg.d_model:
        raise ValueError("program config departs from the stated layers")
    return cfg


class TrainCell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.launch.mesh import make_mesh
        from repro.launch.trainer import build_trainer

        self.ctx = ctx
        c, t = ctx.config, ctx.traffic
        self.n, self.seq = int(t["batch"]), int(t["seq"])
        self.cfg = _program_config(ctx)
        ref = ctx.ref
        mesh = make_mesh((1, 1), ("data", "model"))
        init_state, step_fn, _, _, _ = build_trainer(
            self.cfg, mesh=mesh, optimizer_name="ngd", lr=float(t["lr"]),
            damping=float(t["damping"]), batch=self.n, seq=self.seq,
            total_steps=int(t["total_steps"]), solver=t["solver"],
            momentum=float(t["momentum"]), curvature=t["curvature"], seed=0)
        self.jstep = step_fn.jitted
        pshard, oshard, ishard = step_fn.shardings
        dtype = jnp.dtype(c["dtype"])

        # the parameter tree the reference describes must be the program's
        shapes = ref.param_shapes(c)
        is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
        want = jax.eval_shape(lambda: init_state()["params"])
        got = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype), shapes,
                           is_leaf=is_shape)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("reference parameter tree != program's")
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)]
        treedef = jax.tree.structure(shapes, is_leaf=is_shape)
        leaves = jax.tree.leaves(shapes, is_leaf=is_shape)

        def make_params(key):
            vals = [ref.init_leaf(p, s, jax.random.fold_in(key, i))
                    for i, (p, s) in enumerate(zip(paths, leaves))]
            return jax.tree.unflatten(treedef, vals)

        self.make_params32 = jax.jit(make_params)
        self.make_params = jax.jit(
            lambda k: jax.tree.map(lambda a: a.astype(dtype),
                                   make_params(k)), out_shardings=pshard)
        V, Te, d = c["vocab_size"], c["max_source_positions"], c["d_model"]
        n, seq = self.n, self.seq

        def make_batch(key, i):
            kf, kt = jax.random.split(jax.random.fold_in(key, i))
            toks = jax.random.randint(kt, (n, seq + 1), 3, V, jnp.int32)
            return {"frames": jax.random.normal(kf, (n, Te, d), jnp.float32),
                    "inputs": toks[:, :seq], "labels": toks[:, 1:],
                    "mask": jnp.ones((n, seq), jnp.float32)}

        self.make_batch = jax.jit(make_batch, out_shardings=ishard)
        self.leaf_norms = jax.jit(lambda tree: jnp.stack(
            [jnp.linalg.norm(a.astype(jnp.float32).reshape(-1))
             for a in jax.tree.leaves(tree)]))
        self.delta_norms = jax.jit(lambda a, b: jnp.stack(
            [jnp.linalg.norm((x.astype(jnp.float32) - y.astype(jnp.float32))
                             .reshape(-1))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
        self.paths = paths
        # model FLOPs of one step, for mfu.train
        self.flops = ctx.module("flops").ngd_step_flops(c, n=n, seq=seq)

        self.init_opt = jax.jit(lambda: init_state()["opt"],
                                out_shardings=oshard)

    def start(self):
        """One object, the compiled step with its state, driven from the
        seed through the check steps; their readings are kept."""
        ctx = self.ctx
        self.batch_base = ctx.key(1)
        self.state = {"params": self.make_params(ctx.key(0)),
                      "opt": self.init_opt()}
        self.step_i = 0
        self.losses = []
        for i in range(int(ctx.traffic["check_steps"])):
            self.losses.append(self.step())
            if i == 0:
                self.natgrad_norms = np.asarray(
                    self.leaf_norms(self.state["opt"].momentum))
        p0 = self.make_params(ctx.key(0))
        self.delta_p_norms = np.asarray(
            self.delta_norms(self.state["params"], p0))
        return self

    def batch(self, i):
        return self.make_batch(self.batch_base, np.int32(i))

    def step(self) -> float:
        import jax
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            b = self.batch(self.step_i)
        with jax.profiler.TraceAnnotation("bench.step"):
            p, o, m = self.jstep(self.state["params"], self.state["opt"], b)
            self.state = {"params": p, "opt": o}
            loss = float(m["loss"])
        self.step_i += 1
        return loss

    def window(self, seconds):
        t0 = time.perf_counter()
        steps, bad = 0, 0
        while True:
            loss = self.step()
            steps += 1
            bad += not np.isfinite(loss)
            el = time.perf_counter() - t0
            if el >= seconds:
                break
        return {"attempted": steps, "failed": bad,
                "metrics": {"train_step_ms": el / steps * 1e3},
                "notes": [f"train: {steps} steps in {el:.3f} s"]}

    def release(self):
        self.state = None

    # -- the plain reference over the first steps ---------------------------
    def reference(self, rnd=None):
        """(losses, natural-gradient leaf norms after step 1, parameter-change
        leaf norms after the check steps, reference-gradient leaf norms)."""
        import jax
        import jax.numpy as jnp
        from jax.flatten_util import ravel_pytree

        nr = self.ctx.module("ngd_ref")

        ctx, t, c, ref = self.ctx, self.ctx.traffic, self.ctx.config, \
            self.ctx.ref
        lam, mu = float(t["damping"]), float(t["momentum"])
        n = self.n
        dtype = jnp.dtype(c["dtype"])
        ident = (lambda a: a) if rnd is None else rnd
        precision = nr.HI

        # weights as the program holds them (stored in its dtype)
        params = jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32),
                              self.make_params32(ctx.key(0)))
        p0 = params

        @jax.jit
        def grads(params, batch):
            """Per-sample log-likelihoods and score rows, one sample at a
            time; the mean loss and its gradient v = Sᵀc follow from them."""
            def one(ex):
                lp, g = jax.value_and_grad(
                    lambda q: ref.sample_logp(q, ex, c, ident))(params)
                return lp, ravel_pytree(g)[0]

            lps, G = jax.lax.map(one, batch)
            cnt = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
            return -jnp.sum(lps) / cnt, G / jnp.sqrt(n), cnt

        unravel = ravel_pytree(params)[1]

        @jax.jit
        def natgrad(S, cnt):
            # the loss is the mean over tokens of the per-sample
            # log-likelihoods, so v = ∇loss = Sᵀc with c = −(√n/cnt)·1:
            # x = Sᵀ(SSᵀ+λI)⁻¹c, the solution of (SᵀS+λI)x = v without
            # the cancellation of (v − Sᵀw)/λ
            c = -jnp.sqrt(n) / cnt * jnp.ones((n,), jnp.float32)
            return (unravel(nr.dual_solve(S, c, lam, precision=precision)),
                    unravel(jnp.matmul(S.T, c, precision=precision)))

        buf = jax.tree.map(jnp.zeros_like, params)
        losses, nat_norms, grad_norms = [], None, None
        for i in range(int(t["check_steps"])):
            batch = jax.device_get(self.batch(i))
            loss, S, cnt = grads(params, batch)
            x, g = natgrad(S, cnt)
            del S
            losses.append(float(loss))
            buf = jax.tree.map(lambda b, xi: mu * b + xi, buf, x)
            if i == 0:
                nat_norms = np.asarray(nr.leaf_norms(buf))
                grad_norms = np.asarray(nr.leaf_norms(g))
            lr = nr.warmup_cosine(i, peak=float(t["lr"]),
                                  warmup=max(int(t["total_steps"]) // 20, 1),
                                  total=int(t["total_steps"]))
            params = jax.tree.map(lambda q, b: q - lr * b, params, buf)
        dp = np.asarray(nr.leaf_norms(jax.tree.map(jnp.subtract, params, p0)))
        return losses, nat_norms, dp, grad_norms

    def program_readings(self):
        return self.losses, self.natgrad_norms, self.delta_p_norms

    def check(self):
        """The numbers compared, each with its limit."""
        ref = self.reference()
        self.readings = gaps(self.program_readings(), ref)
        lim = self.ctx.limits
        return {k: (v, float(lim.get(k, float("nan"))))
                for k, v in self.readings.items()}


def worst_leaf_gap(prog, ref, keep):
    """max over kept leaves of |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref[keep]))
    g = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(g[keep]))


def gaps(prog, ref):
    """Program (or control) readings against the reference's. Leaves whose
    reference gradient is under ``MASK_FRAC`` of the median leaf's are
    left out of the two norm gaps."""
    (lp, nat_p, dp_p), (lr, nat_r, dp_r, gn) = prog, ref
    keep = gn >= MASK_FRAC * np.median(gn)
    lp, lr = np.asarray(lp, np.float64), np.asarray(lr, np.float64)
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "natgrad_gap": worst_leaf_gap(nat_p, nat_r, keep),
            "update_gap": worst_leaf_gap(dp_p, dp_r, keep)}


def setup(ctx):
    return TrainCell(ctx).start()


def fp8(a):
    """a rounded to float8 e4m3 (4 exponent, 3 mantissa bits), in float32."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)


def calibrate(make_ctx, seeds, seconds, log):
    """Readings behind the limits, on each seed: the program against the
    reference (lower), the control against it (upper), and the planted
    fault of half the batch left out."""
    cell = None
    for seed in seeds:
        ctx = make_ctx(seed)
        if cell is None:
            cell = TrainCell(ctx)
        cell.ctx = ctx
        cell.start()
        prog = cell.program_readings()
        cell.release()
        ref = cell.reference()
        ctrl = cell.reference(rnd=fp8)
        row = {"seed": seed, "program": gaps(prog, ref),
               "control": gaps(ctrl[:3], ref)}
        make_batch = cell.make_batch

        def half(key, i, _mb=make_batch):
            b = _mb(key, i)
            n = b["mask"].shape[0]
            return dict(b, mask=b["mask"].at[n // 2:].set(0.0))

        cell.make_batch = half
        cell.start()
        fault = cell.program_readings()
        cell.release()
        cell.make_batch = make_batch
        row["fault_half_batch"] = gaps(fault, ref)
        row["program_losses"] = list(map(float, prog[0]))
        row["reference_losses"] = list(map(float, ref[0]))
        row["natgrad_norms"] = {"program": prog[1].tolist(),
                                "reference": ref[1].tolist(),
                                "control": ctrl[1].tolist()}
        row["update_norms"] = {"program": prog[2].tolist(),
                               "reference": ref[2].tolist()}
        yield row
