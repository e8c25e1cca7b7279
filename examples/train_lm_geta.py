"""End-to-end LM training driver with GETA, checkpointing and fault
tolerance — the production loop at reduced scale.

Default config is a ~10M-param model that trains a few hundred steps in
minutes on this CPU container; pass --hundred-m for the ~100M-param variant
(the documented target scale; budget ~1 s/step x steps on CPU, instant on
a real accelerator).

    PYTHONPATH=src python examples/train_lm_geta.py --steps 200

Sharded (data-parallel over N devices; a CPU run held to the CPU with
JAX_PLATFORMS=cpu gets N forced XLA host devices, an accelerator host
uses its chips — add --fsdp to shard params/opt-state):

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/train_lm_geta.py \
        --steps 50 --devices 4
"""
import argparse
import dataclasses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/geta_lm_ckpt")
    ap.add_argument("--inject-failure", type=int, default=None,
                    help="step at which to simulate a node failure")
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel mesh over N devices (CPU runs "
                         "with JAX_PLATFORMS=cpu get N forced XLA host "
                         "devices)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params + optimizer state over the data axis")
    args = ap.parse_args()

    from repro.launch.mesh import force_host_devices
    force_host_devices(args.devices or 0)   # CPU runs only

    from repro.configs import CompressionConfig
    from repro.launch.train import train_loop

    mesh = None
    if args.devices:
        import jax

        from repro.launch.mesh import make_subset_mesh
        n = min(args.devices, jax.device_count())
        if n < args.devices:
            print(f"requested {args.devices} devices, host has "
                  f"{jax.device_count()}; using {n}")
        if args.batch % n != 0:
            raise SystemExit(f"--batch {args.batch} must divide by {n}")
        mesh = make_subset_mesh(n)

    arch = "internlm2-1.8b"
    if args.hundred_m:
        # ~100M params: widen the smoke family
        import repro.configs.internlm2_1_8b as M
        M.SMOKE = dataclasses.replace(
            M.SMOKE, n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
            d_head=64, d_ff=2048, vocab=32000)

    comp = CompressionConfig(
        target_sparsity=0.3, bit_lower=4, bit_upper=16,
        warmup_steps=args.steps // 8,
        projection_periods=2, projection_steps=args.steps // 10,
        pruning_periods=4, pruning_steps=args.steps // 10,
        cooldown_steps=args.steps // 4)
    state, qadg, qasso, losses = train_loop(
        arch, smoke=True, steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, comp=comp,
        inject_failure_at=args.inject_failure,
        mesh=mesh, fsdp=args.fsdp)
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"sparsity={float(qasso.space.sparsity(state['qstate'].keep_mask)):.2f}")


if __name__ == "__main__":
    main()
