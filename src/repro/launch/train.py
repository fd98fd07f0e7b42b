"""Training launcher.

CPU container usage (reduced smoke variant on forced host devices):
    PYTHONPATH=src python -m repro.launch.train --arch gpt3_medium_moe \
        --reduced --devices 4 --mesh-shape 2,2 --steps 50 --aux-mode ta

On a real TPU slice, drop --devices/--reduced and pass --production
(16x16) or --production --multi-pod (2x16x16).
"""

import argparse
import os
import sys


def _deep_tuple(spec):
    if isinstance(spec, int):
        return spec
    return tuple(_deep_tuple(s) for s in spec)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU testing only)")
    ap.add_argument("--mesh-shape", default="1,1",
                    help="data,model (or pod,data,model / "
                         "pod,node,data,model)")
    ap.add_argument("--topology", default="",
                    help="nested topology spec (paper Fig. 2 notation), "
                         "e.g. '[[2,2],[2,2]]' for a 3-tier 8-device "
                         "hierarchy; overrides --mesh-shape's hierarchy "
                         "axes (a trailing model axis of 1 is added)")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--aux-mode", default="ta",
                    choices=["ta", "lb", "hir", "none"])
    ap.add_argument("--aux-weight", type=float, default=1.0)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import ast

    import jax  # noqa: E402,F401  (imported after XLA_FLAGS to pin devices)
    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.configs.base import RunConfig, get_config
    from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                                   mesh_from_topology)
    from repro.training import trainer

    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()

    topo_spec = ()
    if args.topology:
        topo_spec = ast.literal_eval(args.topology)

    if args.production:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif topo_spec:
        mesh = mesh_from_topology(topo_spec)
    else:
        dims = [int(x) for x in args.mesh_shape.split(",")]
        if len(dims) == 4:
            mesh = make_host_mesh(pods=dims[0], nodes=dims[1], data=dims[2],
                                  model=dims[3])
        elif len(dims) == 3:
            mesh = make_host_mesh(pods=dims[0], data=dims[1], model=dims[2])
        else:
            mesh = make_host_mesh(data=dims[0], model=dims[1])

    run = RunConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                    learning_rate=args.lr, total_steps=args.steps,
                    warmup_steps=max(1, args.steps // 10),
                    aux_mode=args.aux_mode, aux_weight=args.aux_weight,
                    microbatch=args.microbatch, remat=args.remat,
                    seed=args.seed, topology=_deep_tuple(topo_spec))
    res = trainer.train(arch, run, mesh, steps=args.steps,
                        aux_mode=args.aux_mode, log_every=args.log_every,
                        ckpt_path=args.ckpt)
    print(f"done: {args.steps} steps, {res.steps_per_sec:.3f} steps/s, "
          f"final loss {res.losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
