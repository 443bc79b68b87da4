"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma_7b --smoke \
        --steps 20 --data-parallel 2 --model-parallel 2

On a real TPU fleet this process runs per host (jax.distributed.initialize
picks up the coordinator from the environment); in this container the mesh
axes map onto however many host devices XLA_FLAGS exposes.  XLA flags for the
latency-hiding scheduler (collective overlap on TPU) are recorded here and
applied when the backend is TPU.
"""
import argparse
import os

TPU_XLA_FLAGS = (
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--deadline-s", type=float, default=0.0)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if jax.default_backend() == "tpu":
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + TPU_XLA_FLAGS

    from repro.configs import get
    from repro.data import DataConfig, token_stream
    from repro.parallel import ParallelCtx
    from repro.training import TrainConfig, Trainer

    cfg = get(args.arch, smoke=args.smoke)
    pctx = None
    if args.data_parallel * args.model_parallel > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(args.data_parallel, args.model_parallel)
        pctx = ParallelCtx(mesh=mesh, data_axes=("data",))
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch, seed=0)
    tc = TrainConfig(n_microbatches=args.microbatches, remat=True, zero1=True,
                     total_steps=max(args.steps, 100),
                     warmup=max(5, args.steps // 10),
                     checkpoint_every=max(10, args.steps // 3),
                     checkpoint_dir=args.ckpt,
                     step_deadline_s=args.deadline_s)

    def run():
        tr = Trainer(cfg, tc, token_stream(dc, 0), pctx=pctx)
        if args.resume:
            tr.restore_if_available()
        log = tr.run(args.steps)
        for m in log[:3] + log[-3:]:
            print({k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in m.items()})
        if tr.skipped_steps:
            print(f"straggler violations: {len(tr.skipped_steps)}")

    if pctx is not None:
        with pctx.mesh:
            run()
    else:
        run()


if __name__ == "__main__":
    main()
