"""Serving launcher: batched prefill + decode on a mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
        --reduced --devices 4 --mesh-shape 2,2 --batch 4 --steps 16
"""

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh-shape", default="1,1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--streams", type=int, default=0,
                    help="serve this many queued requests through the "
                         "continuous-batching engine instead of one "
                         "fixed-batch generate call")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.enable()
    from repro import sharding
    from repro.configs.base import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib
    from repro.serving import engine

    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()
    dims = [int(x) for x in args.mesh_shape.split(",")]
    mesh = make_host_mesh(data=dims[0], model=dims[1])

    ctx = model_lib.build_ctx(arch, mesh, seq_len=args.cache_len,
                              global_batch=args.batch, aux_mode="none")
    rules = model_lib.default_rules(mesh)
    with mesh, sharding.axis_rules(rules):
        params = model_lib.init_params(jax.random.PRNGKey(0), ctx,
                                       rules=rules)
        if args.streams:
            import numpy as np
            from repro.serving.scheduler import Request
            rng = np.random.default_rng(1)
            reqs = [Request(uid=i,
                            tokens=rng.integers(
                                0, arch.vocab_size,
                                size=args.prompt_len).tolist(),
                            max_new_tokens=args.steps,
                            temperature=args.temperature)
                    for i in range(args.streams)]
            cfg = engine.ServeConfig(num_slots=args.batch,
                                     cache_len=args.cache_len,
                                     prefill_pack=min(args.batch, 4),
                                     prompt_buckets=(args.prompt_len,))
            report = engine.ServingEngine(params, ctx, cfg).run(reqs)
            print(f"served {len(report.streams)} streams at "
                  f"{report.tokens_per_sec:.2f} tok/s aggregate "
                  f"({report.decode_steps} decode steps, "
                  f"{report.prefill_calls} prefill packs)")
            return 0
        key = jax.random.PRNGKey(1)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len),
                                     0, arch.vocab_size, jnp.int32)
        res = engine.generate(params, ctx, prompts, steps=args.steps,
                              cache_len=args.cache_len,
                              temperature=args.temperature)
    print(f"generated {res.tokens.shape} tokens at "
          f"{res.steps_per_sec:.2f} decode steps/s")
    print("sample:", res.tokens[0][:16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
