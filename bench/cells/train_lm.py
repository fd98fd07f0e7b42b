"""Training cells: the trainer's jitted, donated step, its data pipeline and
its logging cadence, driven for a window of wall time.

Set-up makes the weights from the seed, builds the step, and drives it
through the traffic's ``checked_steps`` first steps with the window's own
call and feed (``SyntheticLM`` rows of each step differ).  The same state
then goes on into the window, which runs from one synced (logged) step to
another.  After the window the program's state is freed and the plain
reference repeats those first steps from the same weights and rows.

Compared, each as a relative gap to the reference:

* ``grad``   -- the first gradient as the optimizer got it, worked out
  from its first moment after one step (mu / (1 - b1)), leaf by leaf by
  norm, against the larger of the leaf's reference norm and the median
  leaf's: the widest leaf's gap;
* ``grad_median`` -- the median leaf's gap of the same.  The widest leaf
  is an expert stack whose gap follows the tokens that a bf16 gate moves
  across an expert's capacity, so it swings from seed to seed and the
  float8 control can read as low as sound runs do; the median leaf is
  steady and holds the control apart (see PERF.md);
* ``change`` -- each leaf's change over the checked steps, by norm, the
  same way; leaves whose reference gradient is under a thousandth of the
  median leaf's are left out (they move by round-off alone).

The first step's loss gap (``loss``) is reported beside them but not
compared: neither the float8 control nor a planted fault reads it far
enough above sound runs to set a limit between (see PERF.md).
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness, weights
from bench.reference import moe_lm

#: AdamW's b1 in the program's optimizer (``repro.optim.adamw``).
B1 = 0.9


class Program:
    """The program's objects for one cell: context, step and data."""

    def __init__(self, cell: harness.Cell, devices, seed: int):
        import jax

        from repro import sharding
        from repro.configs.base import RunConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as model_lib
        from repro.training import trainer

        t = cell.traffic
        o = t["optimizer"]
        self.spec = moe_lm.spec(cell.config)
        self.arch = harness.program_arch(cell.config)
        harness.check_arch(self.arch, self.spec)
        self.chips = len(devices)
        self.run = RunConfig(
            seq_len=t["seq_len"], global_batch=t["batch_per_chip"] * self.chips,
            learning_rate=o["learning_rate"], warmup_steps=o["warmup_steps"],
            total_steps=o["total_steps"], weight_decay=o["weight_decay"],
            grad_clip=o["grad_clip"], aux_weight=o["aux_weight"],
            aux_mode=t["aux_mode"], dispatch=t["dispatch"],
            remat=t.get("remat", False), seed=seed)
        run = self.run
        self.mesh = make_host_mesh(data=self.chips, model=1)
        # the context trainer.train builds for this run
        self.ctx = model_lib.build_ctx(
            self.arch, self.mesh, seq_len=run.seq_len,
            global_batch=run.global_batch, aux_mode=run.aux_mode,
            remat=run.remat, dispatch=run.dispatch,
            a2a_num_chunks=run.a2a_num_chunks,
            dispatch_override=run.dispatch_override, use_pallas=run.use_pallas,
            wire_codec=run.wire_codec, resilience=None)
        self.rules = model_lib.default_rules(self.mesh)
        self.log_every = t["log_every"]
        self.step = jax.jit(trainer.make_train_step(self.ctx, run),
                            donate_argnums=(0, 1))
        self.abstract = model_lib.abstract_params(jax.random.PRNGKey(0),
                                                  self.ctx)
        self.data = SyntheticLM(DataConfig(
            vocab_size=self.arch.vocab_size, seq_len=run.seq_len,
            global_batch=run.global_batch, seed=seed), self.arch)
        self._scope = (self.mesh, sharding.axis_rules(self.rules))

    def __enter__(self):
        for c in self._scope:
            c.__enter__()
        return self

    def __exit__(self, *exc):
        for c in reversed(self._scope):
            c.__exit__(*exc)

    def feed(self, i: int):
        from repro.data.pipeline import shard_batch
        host = self.data.batch(i)
        return host, shard_batch(host, self.mesh)


def _leaf_norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree, other):
        a = jax.tree_util.tree_leaves(tree)
        b = jax.tree_util.tree_leaves(other) if other is not None else None
        out = []
        for i, x in enumerate(a):
            x = x.astype(jnp.float32)
            if b is not None:
                x = x - b[i].astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(x * x)))
        return jnp.stack(out)
    return norms


def log(metrics) -> dict:
    """The trainer's logging of a step: scalars to floats, vectors to lists
    (a sync with the device)."""
    return {k: (float(v) if getattr(v, "ndim", 0) == 0
                else [float(x) for x in v]) for k, v in metrics.items()}


def first_steps(prog: Program, seed: int, n: int):
    """Weights from the seed, then ``n`` steps through the window's call
    and feed.  Returns (params, opt_state, readings, host batches)."""
    import jax

    from repro.optim import adamw
    norms = _leaf_norms()
    params = weights.make(prog.abstract, seed, placed=True)
    opt = adamw.init_state(params)
    jax.block_until_ready((params, opt))
    harness.mark("weights")
    losses, rows, grad = [], [], None
    for i in range(n):
        host, batch = prog.feed(i)
        rows.append({k: np.asarray(host[k]) for k in ("tokens", "labels")})
        params, opt, m = prog.step(params, opt, batch)
        losses.append(log(m)["loss"])
        if i == 0:
            grad = np.asarray(norms(opt["mu"], None)) / (1 - B1)
    p0 = weights.make(prog.abstract, seed, placed=True)
    change = np.asarray(norms(params, p0))
    del p0
    return params, opt, {"losses": losses, "grad": grad,
                         "change": change}, rows


def window(prog: Program, params, opt, seconds: float, start: int):
    """Steps from ``start`` on; stops at the first logged step at or past
    ``seconds``.  Returns (params, opt, steps, t0, t1, logged metrics)."""
    import jax

    log_every = prog.log_every
    ann = jax.profiler.TraceAnnotation
    hist, i = [], start
    t0 = time.perf_counter()
    while True:
        with ann("bench.data"):
            _, batch = prog.feed(i)
        with ann("bench.step"):
            params, opt, m = prog.step(params, opt, batch)
        if i % log_every == 0:
            with ann("bench.log"):
                hist.append(log(m))
            if time.perf_counter() - t0 >= seconds:
                i += 1
                break
        i += 1
    t1 = time.perf_counter()
    return params, opt, i - start, t0, t1, hist


def reference(cell: harness.Cell, abstract, seed: int, rows, prec: str):
    """The plain reference (``prec`` "f32", or "fp8" for the control)
    through the same first steps from the same weights and rows; readings
    as :func:`first_steps` gives them."""
    import jax
    import jax.numpy as jnp

    s = moe_lm.spec(cell.config)
    t = cell.traffic
    o = t["optimizer"]
    tokens = t["batch_per_chip"] * t["seq_len"]
    cap = moe_lm.capacity_for(s, tokens)
    row_block = 2048 if tokens % 2048 == 0 else tokens
    flat, treedef = jax.tree_util.tree_flatten(abstract)
    stored = [jnp.dtype(a.dtype) for a in flat]

    def step(p, mu, nu, batch, lr, c1, c2):
        params = jax.tree_util.tree_unflatten(treedef, p)
        (loss, _), g = jax.value_and_grad(moe_lm.lm_loss, has_aux=True)(
            params, batch, s, prec, cap, o["aux_weight"], row_block)
        p, mu, nu, gc = moe_lm.adamw(p, jax.tree_util.tree_leaves(g), mu, nu,
                                     lr, c1, c2, o, stored)
        return p, mu, nu, loss, jnp.stack([jnp.sqrt(jnp.sum(x * x))
                                           for x in gc])
    with jax.default_matmul_precision("highest"):
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        p = [x.astype(jnp.float32) for x in
             jax.tree_util.tree_leaves(weights.make(abstract, seed))]
        mu = [jnp.zeros_like(x) for x in p]
        nu = [jnp.zeros_like(x) for x in p]
        losses, grad = [], None
        for i, r in enumerate(rows):
            lr, c1, c2 = moe_lm.step_scalars(o, i + 1)
            p, mu, nu, loss, gn = step(p, mu, nu, r, jnp.float32(lr),
                                       jnp.float32(c1), jnp.float32(c2))
            losses.append(float(loss))
            if i == 0:
                grad = np.asarray(gn)
        del mu, nu
        p0 = jax.tree_util.tree_leaves(weights.make(abstract, seed))
        change = np.asarray(jnp.stack([
            jnp.sqrt(jnp.sum((a - b.astype(jnp.float32)) ** 2))
            for a, b in zip(p, p0)]))
    return {"losses": losses, "grad": grad, "change": change}


def numbers(prog: dict, ref: dict) -> dict:
    """``grad``, ``grad_median`` and ``change``, compared, and ``loss``,
    reported (see the module docstring)."""
    lp, lr_ = prog["losses"][0], ref["losses"][0]
    loss = abs(lp - lr_) / abs(lr_)
    gr = ref["grad"]
    med = float(np.median(gr))
    gaps = np.abs(prog["grad"] - gr) / np.maximum(gr, med)
    keep = gr >= 1e-3 * med
    cr = ref["change"][keep]
    cmed = float(np.median(cr))
    change = float(np.max(np.abs(prog["change"][keep] - cr)
                          / np.maximum(cr, cmed)))
    return {"loss": loss, "grad": float(np.max(gaps)),
            "grad_median": float(np.median(gaps)), "change": change}


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices) -> dict:
    t = cell.traffic
    n_check = t["checked_steps"]
    with Program(cell, devices, seed) as prog:
        harness.mark("program")
        params, opt, got, rows = first_steps(prog, seed, n_check)
        harness.mark("checked_steps")
        if trace:
            harness.start_trace()
        compiles = harness.CompileCounter()
        compiles.arm()
        with harness.HostWatch() as watch:
            params, opt, steps, t0, t1, hist = window(prog, params, opt,
                                                      seconds, n_check)
        loads = compiles.disarm()
        tr = harness.stop_trace() if trace else None
        peak = harness.peak_memory(devices)
        abstract = prog.abstract
        tokens = steps * prog.run.global_batch * prog.run.seq_len
        spec = prog.spec
    del params, opt, prog
    harness.free_device_memory(devices)
    ref = reference(cell, abstract, seed, rows, "f32")
    compared = numbers(got, ref)
    correct, checks = harness.judge(compared, cell.limits)

    win = t1 - t0
    losses = [h["loss"] for h in hist]
    failed = sum(1 for v in losses if not np.isfinite(v))
    dev = harness.device_info(devices, peak)
    result = {"correct": correct and failed == 0, "attempted": steps,
              "failed": failed}
    if trace:
        info = {"kind": "train", "spec": spec, "traffic": t,
                "chips": len(devices), "peak": harness.peaks(dev["kind"]),
                "window_s": win, "trace": tr, "tokens": tokens,
                "steps": steps,
                "dropped": float(np.mean([h.get("dropped", 0.0)
                                          for h in hist]))}
        result["metrics"] = harness.read_per_layer(cell.per_layer, info)
        dev.update(harness.trace_device_fields(tr, win, len(devices)))
        result["device"] = dev
        result["breakdown"] = harness.breakdown(tr)
    else:
        e2e = {"train_tokens_per_s": tokens / win,
               "setup_s": t0 - harness.T_START}
        result["metrics"] = {m["name"]: harness.metric_value(
            e2e[m["name"]], m["unit"]) for m in cell.end_to_end}
        result["device"] = dev
    result["window"] = {"seconds": win, "steps": steps,
                        **loads, **watch.report(),
                        "setup_marks": harness.SETUP_MARKS,
                        "first_losses": got["losses"],
                        "first_loss_gap": compared["loss"]}
    result["checks"] = checks
    return result
