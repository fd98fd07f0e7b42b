"""Expert-parallel training cells: the ``train_lm`` cell over several chips.

The program, its first checked steps, the window and the compared numbers
are ``train_lm``'s (``Program`` builds ``make_host_mesh(data=chips)``, so
the experts spread over the chips and every MoE layer's tokens cross them
in all-to-alls).  Three things differ:

* set-up refuses a program whose TA dispatch plan (the one stage's
  per-(source chip, expert) capacity) or TA penalties disagree with the
  configuration file's ``ta_plan``, as ``harness.check_arch`` does for
  widths, and one that does not report the share of its traffic that
  stays on each chip (``frac_self``);
* the reference is ``moe_lm_ep``'s, split by source chip, its float32
  state spread over the cell's chips (expert stacks on the expert axis, as
  the program holds them; the batch by source);
* the window's output holds the mean traffic shares the trainer logs:
  ``frac_by_level``, ``frac_self`` (remote = 1 - self) and ``dropped``.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench import harness, weights
from bench.cells import train_lm
from bench.reference import moe_lm, moe_lm_ep

#: The window means written into the output, from the logged metrics.
SHARES = ("frac_by_level", "frac_self", "dropped")


def check_plan(ctx, config: dict) -> None:
    """Refuses to run where the program's dispatch plan or TA penalties
    differ from the file's ``ta_plan``, or where the program does not
    report its self share."""
    want = config["ta_plan"]
    if "frac_self" not in getattr(ctx, "traffic_shares", ()):
        raise ValueError("the program does not report the share of its "
                         "traffic that stays on each chip (frac_self)")
    plan, gate = ctx.plan, ctx.gate_cfg
    n = len(want["penalties"])
    got = {"tokens_per_device": plan.tokens_per_device,
           "capacity": tuple(plan.caps),
           "penalties": tuple(gate.penalty_by_level[:n])}
    wanted = {"tokens_per_device": want["tokens_per_device"],
              "capacity": (want["capacity"],),
              "penalties": tuple(want["penalties"])}
    bad = {k: (got[k], wanted[k]) for k in wanted
           if k != "penalties" and got[k] != wanted[k]}
    if not all(math.isclose(a, b, rel_tol=1e-9)
               for a, b in zip(got["penalties"], wanted["penalties"])):
        bad["penalties"] = (got["penalties"], wanted["penalties"])
    if bad:
        raise ValueError(f"program dispatch plan disagrees with the file: "
                         f"{bad}")


def program(cell: harness.Cell, devices, seed: int) -> train_lm.Program:
    prog = train_lm.Program(cell, devices, seed)
    check_plan(prog.ctx, cell.config)
    return prog


class Reference:
    """The per-source reference of one cell for one parameter layout
    (``abstract``), its float32 state on the shardings ``abstract``
    carries.  Its step is compiled ahead of time, once for each precision
    and batch shape (the exchange-left-out fault is an argument of the
    step, not a program of its own); :meth:`prepare` starts a compile on a
    worker thread, so that a caller can overlap it with other work."""

    def __init__(self, cell: harness.Cell, abstract):
        import concurrent.futures

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.cell, self.abstract = cell, abstract
        self.spec = moe_lm.spec(cell.config)
        flat, self.treedef = jax.tree_util.tree_flatten(abstract)
        self.stored = [a.dtype for a in flat]
        self.shards = [a.sharding for a in flat]
        mesh = self.shards[0].mesh
        self.by_source = NamedSharding(mesh, P(mesh.axis_names[0]))
        self.replicated = NamedSharding(mesh, P())
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=2)
        self._compiled = {}

    def _compile(self, prec: str, batch_shape: tuple):
        import jax
        import jax.numpy as jnp

        s, o = self.spec, self.cell.traffic["optimizer"]
        plan = self.cell.config["ta_plan"]
        treedef, stored = self.treedef, self.stored

        def step(p, mu, nu, batch, lr, c1, c2, local_only):
            moe_fn = functools.partial(
                moe_lm_ep.moe, capacity=plan["capacity"],
                sources=self.cell.workload["chips"],
                penalties=tuple(plan["penalties"]), local_only=local_only)
            params = jax.tree_util.tree_unflatten(treedef, p)
            (loss, _), g = jax.value_and_grad(
                moe_lm_ep.lm_loss, has_aux=True)(
                    params, batch, s, prec, moe_fn, o["aux_weight"])
            p, mu, nu, gc = moe_lm.adamw(p, jax.tree_util.tree_leaves(g),
                                         mu, nu, lr, c1, c2, o, stored)
            return p, mu, nu, loss, jnp.stack([jnp.sqrt(jnp.sum(x * x))
                                               for x in gc])

        def sds(shape, dtype, sharding):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        p = [sds(a.shape, jnp.float32, a.sharding)
             for a in jax.tree_util.tree_leaves(self.abstract)]
        batch = {k: sds(batch_shape, jnp.int32, self.by_source)
                 for k in ("tokens", "labels")}
        scalar = sds((), jnp.float32, self.replicated)
        sh = self.shards
        # the precision is a context of the thread that traces
        with jax.default_matmul_precision("highest"):
            return jax.jit(step, donate_argnums=(0, 1, 2),
                           out_shardings=(sh, sh, sh, None, None)).lower(
                p, p, p, batch, scalar, scalar, scalar,
                sds((), jnp.bool_, self.replicated)).compile()

    def prepare(self, prec: str, batch_shape):
        """The future of the step compiled for ``prec`` ("f32" or "fp8")
        and batches of ``batch_shape``, started once."""
        key = (prec, tuple(batch_shape))
        if key not in self._compiled:
            self._compiled[key] = self._pool.submit(self._compile, *key)
        return self._compiled[key]

    def run(self, seed: int, rows, prec: str, local_only: bool = False):
        """Through the first steps from the seed's weights and ``rows``
        (``prec`` "f32" or "fp8"; ``local_only`` the exchange-left-out
        fault); readings as ``train_lm.first_steps`` gives them."""
        import jax
        import jax.numpy as jnp

        o = self.cell.traffic["optimizer"]
        step = self.prepare(prec, rows[0]["tokens"].shape).result()

        def scalar(v, dtype=np.float32):
            return jax.device_put(np.asarray(v, dtype), self.replicated)
        p = [x.astype(jnp.float32) for x in jax.tree_util.tree_leaves(
            weights.make(self.abstract, seed, placed=True))]
        mu = [jnp.zeros_like(x) for x in p]
        nu = [jnp.zeros_like(x) for x in p]
        losses, grad = [], None
        for i, r in enumerate(rows):
            lr, c1, c2 = moe_lm.step_scalars(o, i + 1)
            batch = jax.device_put(r, self.by_source)
            p, mu, nu, loss, gn = step(p, mu, nu, batch, scalar(lr),
                                       scalar(c1), scalar(c2),
                                       scalar(local_only, np.bool_))
            losses.append(float(loss))
            if i == 0:
                grad = np.asarray(gn)
        del mu, nu
        p0 = jax.tree_util.tree_leaves(weights.make(self.abstract, seed,
                                                    placed=True))
        change = np.asarray(jnp.stack([
            jnp.sqrt(jnp.sum((a - b.astype(jnp.float32)) ** 2))
            for a, b in zip(p, p0)]))
        return {"losses": losses, "grad": grad, "change": change}


#: Op kinds the traced breakdown sums, by a pattern of the op's text.
KINDS = {"all-to-all": r"all-to-all|all_to_all",
         "all-reduce": r"all-reduce|all_reduce",
         "all-gather": r"all-gather|all_gather",
         "reduce-scatter": r"reduce-scatter|reduce_scatter",
         "collective-permute": r"collective-permute|collective_permute",
         "pallas": r"tpu_custom_call"}


def by_kind(tr, chips: int) -> dict:
    """Mean seconds over the chips of the ops of each kind, and of the
    part of each collective kind in which no other leaf op runs (as
    ``a2a_exposed_share.train`` counts it)."""
    import re

    from bench import trace as trace_lib
    from bench.metrics import _ops
    devs = tr.devices[:chips]
    out = {"busy_s": sum(trace_lib.busy_s(d) for d in devs) / len(devs)}
    for kind, pat in KINDS.items():
        rx = re.compile(pat)

        def mine(name, rx=rx):
            return rx.search(name) is not None
        out[kind + "_s"] = sum(_ops.runs(d, mine)[1]
                               for d in devs) / len(devs)
        if kind != "pallas":
            out[kind + "_exposed_s"] = sum(_ops.exposed_s(d, mine)
                                           for d in devs) / len(devs)
    return out


def shares(hist) -> dict:
    """The window's mean of each traffic share the logged steps hold."""
    out = {}
    for k in SHARES:
        vals = [h[k] for h in hist if k in h]
        if vals:
            out[k] = np.mean(np.asarray(vals, np.float64), 0).tolist()
    return out


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices) -> dict:
    t = cell.traffic
    n_check = t["checked_steps"]
    with program(cell, devices, seed) as prog:
        harness.mark("program")
        params, opt, got, rows = train_lm.first_steps(prog, seed, n_check)
        harness.mark("checked_steps")
        if trace:
            harness.start_trace()
        compiles = harness.CompileCounter()
        compiles.arm()
        with harness.HostWatch() as watch:
            params, opt, steps, t0, t1, hist = train_lm.window(
                prog, params, opt, seconds, n_check)
        loads = compiles.disarm()
        tr = harness.stop_trace() if trace else None
        peak = harness.peak_memory(devices)
        abstract = prog.abstract
        tokens = steps * prog.run.global_batch * prog.run.seq_len
        spec = prog.spec
    del params, opt, prog
    harness.free_device_memory(devices)
    ref = Reference(cell, abstract).run(seed, rows, "f32")
    compared = train_lm.numbers(got, ref)
    correct, checks = harness.judge(compared, cell.limits)

    win = t1 - t0
    losses = [h["loss"] for h in hist]
    failed = sum(1 for v in losses if not np.isfinite(v))
    dev = harness.device_info(devices, peak)
    mean = shares(hist)
    result = {"correct": correct and failed == 0, "attempted": steps,
              "failed": failed}
    if trace:
        info = {"kind": "train", "spec": spec, "traffic": t,
                "chips": len(devices), "peak": harness.peaks(dev["kind"]),
                "window_s": win, "trace": tr, "tokens": tokens,
                "steps": steps, "dropped": mean.get("dropped", 0.0),
                "capacity": cell.config["ta_plan"]["capacity"]}
        result["metrics"] = harness.read_per_layer(cell.per_layer, info)
        dev.update(harness.trace_device_fields(tr, win, len(devices)))
        result["device"] = dev
        result["breakdown"] = {**harness.breakdown(tr),
                               "by_kind": by_kind(tr, len(devices))}
    else:
        e2e = {"train_tokens_per_s": tokens / win,
               "setup_s": t0 - harness.T_START}
        result["metrics"] = {m["name"]: harness.metric_value(
            e2e[m["name"]], m["unit"]) for m in cell.end_to_end}
        result["device"] = dev
    result["window"] = {"seconds": win, "steps": steps, **mean,
                        **loads, **watch.report(),
                        "setup_marks": harness.SETUP_MARKS,
                        "first_losses": got["losses"],
                        "first_loss_gap": compared["loss"]}
    result["checks"] = checks
    return result
