"""Serving cells: ``ServingEngine.run`` over a fixed list of requests, all
queued at the start (offline continuous batching).

Set-up makes the weights from the seed, builds the engine, and warms it on
a few short requests that cover every prompt bucket, so every program the
window drives is compiled.  The window is one ``run`` of that same engine
over ``requests_per_second x seconds`` requests.  Their lengths are the
same, in the same order, for every seed -- quantiles of the traffic's
lognormal laws, shuffled once by the traffic's ``order_seed`` -- and the
seed draws the prompt tokens, so every seed does the same work: the order
decides how full the slots stay as the batch drains, which moved the rate
by 9% from one order to another.  Sampling is greedy.

Compared, after the window: a sample of the finished requests drawn from
the seed, the longest among them, runs once through the plain reference
over its prompt and served tokens.  At each served position the gap is the
margin by which the served token's reference logit lies below the
reference's best (0 where the program served the reference's argmax).
``far_share`` is the share of served tokens whose gap exceeds ``FAR``,
half a logit.  The widest gap is reported beside it but not compared: at
these widths, with random weights, a bf16 router flips experts and the
vocabulary is near-tied, so single positions reach 0.7-0.9 logit in sound
runs and the float8 control reaches little more (see PERF.md).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from bench import counts, harness, weights
from bench.reference import moe_lm

#: Gap (in logits) past which a served token counts as far from the
#: reference's choice.
FAR = 0.5


class Program:
    def __init__(self, cell: harness.Cell, devices, seed: int):
        import jax

        from repro import sharding
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as model_lib
        from repro.serving import engine

        t = cell.traffic
        self.spec = moe_lm.spec(cell.config)
        self.arch = harness.program_arch(cell.config)
        harness.check_arch(self.arch, self.spec)
        self.cfg = engine.ServeConfig(
            num_slots=t["num_slots"], cache_len=t["cache_len"],
            prefill_pack=t["prefill_pack"],
            prompt_buckets=tuple(t["prompt_buckets"]))
        self.mesh = make_host_mesh(data=len(devices), model=1)
        self.ctx = model_lib.build_ctx(self.arch, self.mesh,
                                       seq_len=t["cache_len"],
                                       global_batch=t["num_slots"],
                                       aux_mode="none")
        self.abstract = model_lib.abstract_params(jax.random.PRNGKey(0),
                                                  self.ctx)
        self._scope = (self.mesh, sharding.axis_rules(
            model_lib.default_rules(self.mesh)))

    def __enter__(self):
        for c in self._scope:
            c.__enter__()
        return self

    def __exit__(self, *exc):
        for c in reversed(self._scope):
            c.__exit__(*exc)

    def engine(self, seed: int):
        from repro.serving import engine
        params = weights.make(self.abstract, seed, placed=True)
        return engine.ServingEngine(params, self.ctx, self.cfg)


def lengths(law: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal law with
    the given median and sigma, clipped to [min, max]."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.rint(law["median"] * np.exp(law["sigma"] * z))
    return np.clip(v, law["min"], law["max"]).astype(int)


def requests(traffic: dict, vocab: int, seed: int, n: int):
    from repro.serving.scheduler import Request
    order = np.random.default_rng(traffic["order_seed"])
    p = order.permutation(lengths(traffic["prompt"], n))
    o = order.permutation(lengths(traffic["output"], n))
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, vocab, int(p[i])).tolist(),
                    max_new_tokens=int(o[i]), temperature=0.0)
            for i in range(n)]


def warm_requests(traffic: dict, vocab: int):
    """One full pack per prompt bucket, two new tokens each."""
    from repro.serving.scheduler import Request
    out = []
    for b in traffic["prompt_buckets"]:
        L = min(b, traffic["cache_len"] - 2)
        out += [Request(uid=-1 - len(out), tokens=[1] * L, max_new_tokens=2)
                for _ in range(traffic["prefill_pack"])]
    return out


def sample(streams, check: dict, seed: int):
    """The longest finished request, then others in an order drawn from the
    seed, until ``min_tokens`` served tokens or ``max_requests``."""
    done = [s for s in streams if s.generated]
    longest = max(done, key=lambda s: s.request.prompt_len + len(s.generated))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 1])
    out, n = [longest], len(longest.generated)
    for i in rng.permutation(len(rest)):
        if n >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
        n += len(rest[i].generated)
    return [(list(s.request.tokens), list(s.generated)) for s in out]


def reference_gaps(cell: harness.Cell, abstract, seed: int, picked,
                   control: bool = False):
    """Per request, at its served positions: (the program's gaps, the
    control's gaps, the reference's margin between its best and
    second-best logit), the last two None without ``control``.  The
    control puts first the token that the reference computed in float8
    puts first; see the module docstring."""
    import jax
    import jax.numpy as jnp

    s = moe_lm.spec(cell.config)
    L = cell.traffic["cache_len"]

    def fn(params, seq):
        feats, _ = moe_lm.features(params, seq[None], s, "f32")
        lg = moe_lm.logits(params, feats[0], "f32")            # [L, V]
        best = lg.max(-1)
        nxt = jnp.concatenate([seq[1:], seq[:1]])
        served = best - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
        if not control:
            return served, served, served
        cf, _ = moe_lm.features(params, seq[None], s, "fp8")
        pick = jnp.argmax(moe_lm.logits(params, cf[0], "fp8"), -1)
        top2 = jax.lax.top_k(lg, 2)[0]
        return (served, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0],
                top2[:, 0] - top2[:, 1])

    out = []
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(fn)
        params = weights.make(abstract, seed)
        for prompt, gen in picked:
            seq = np.zeros((L,), np.int32)
            full = prompt + gen
            seq[:len(full)] = full
            a, b, m = fn(params, jnp.asarray(seq))
            lo, hi = len(prompt) - 1, len(full) - 1
            cut = lambda x: np.asarray(x)[lo:hi]  # noqa: E731
            out.append((cut(a), cut(b), cut(m)) if control
                       else (cut(a), None, None))
    return out


def served_counts(spec, streams) -> dict:
    """Model FLOPs and cache rows of the finished requests: each prompt's
    prefill, and each later token decoded against its context."""
    pre_f = dec_f = rows = 0.0
    pre_tok = dec_tok = 0
    for st in streams:
        pl, n = st.request.prompt_len, len(st.generated)
        pre_f += counts.prefill_flops(spec, pl)
        pre_tok += pl
        for j in range(1, n):
            dec_f += counts.decode_flops(spec, pl + j)
            rows += pl + j
        dec_tok += max(0, n - 1)
    return {"prefill_flops": pre_f, "decode_flops": dec_f,
            "prefill_tokens": pre_tok, "decode_tokens": dec_tok,
            "cache_rows": rows}


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices) -> dict:
    import jax
    t = cell.traffic
    with Program(cell, devices, seed) as prog:
        harness.mark("program")
        vocab = prog.arch.vocab_size
        eng = prog.engine(seed)
        eng.params = jax.block_until_ready(eng.params)
        harness.mark("weights")
        eng.run(warm_requests(t, vocab), seed=seed)
        harness.mark("warm")
        reqs = requests(t, vocab, seed,
                        max(1, round(t["requests_per_second"] * seconds)))
        if trace:
            harness.start_trace()
        compiles = harness.CompileCounter()
        compiles.arm()
        with harness.HostWatch() as watch:
            t0 = time.perf_counter()
            report = eng.run(reqs, seed=seed)
            t1 = time.perf_counter()
        loads = compiles.disarm()
        tr = harness.stop_trace() if trace else None
        peak = harness.peak_memory(devices)
        abstract, spec, cfg = prog.abstract, prog.spec, prog.cfg
        streams = report.streams
        picked = sample(streams, t["check"], seed)
        del eng
    harness.free_device_memory(devices)
    gaps = np.concatenate([g[0] for g in
                           reference_gaps(cell, abstract, seed, picked)])
    correct, checks = harness.judge(
        {"far_share": float(np.mean(gaps > FAR))}, cell.limits)

    win = t1 - t0
    want = {r.uid: r.max_new_tokens for r in reqs}
    ok = sum(1 for st in streams if not st.evicted
             and len(st.generated) == want.get(st.request.uid, -1))
    failed = len(reqs) - ok
    dev = harness.device_info(devices, peak)
    result = {"correct": correct and failed == 0, "attempted": len(reqs),
              "failed": failed}
    if trace:
        info = {"kind": "serve", "spec": spec, "traffic": t,
                "chips": len(devices), "peak": harness.peaks(dev["kind"]),
                "window_s": win, "trace": tr,
                "decode_steps": report.decode_steps,
                "prefill_calls": report.prefill_calls,
                "num_slots": cfg.num_slots, "prefill_pack": cfg.prefill_pack,
                **served_counts(spec, streams)}
        result["metrics"] = harness.read_per_layer(cell.per_layer, info)
        dev.update(harness.trace_device_fields(tr, win, len(devices)))
        result["device"] = dev
        result["breakdown"] = harness.breakdown(tr)
    else:
        tpot = [1e3 * (st.t_finished - st.t_admitted) / len(st.generated)
                for st in streams if st.generated]
        e2e = {"serve_tokens_per_s": report.total_new_tokens / win,
               "serve_tpot_p95_ms": float(np.percentile(tpot, 95)),
               "setup_s": t0 - harness.T_START}
        result["metrics"] = {m["name"]: harness.metric_value(
            e2e[m["name"]], m["unit"]) for m in cell.end_to_end}
        result["device"] = dev
    result["window"] = {"seconds": win, "requests": len(reqs),
                        "tokens": report.total_new_tokens,
                        "decode_steps": report.decode_steps,
                        "prefill_calls": report.prefill_calls,
                        **loads, **watch.report(),
                        "setup_marks": harness.SETUP_MARKS,
                        "checked_tokens": int(gaps.size),
                        "widest_gap": float(gaps.max())}
    result["checks"] = checks
    return result
