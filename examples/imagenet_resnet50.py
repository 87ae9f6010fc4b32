"""ImageNet ResNet-50 data-parallel — the headline workload (BASELINE
config 3; target: >=90% scaling efficiency img/s/chip on v5e-64).

Reference analog: fb.resnet.torch ResNet-50 under ``torchmpi.nn``
(SURVEY.md §8.1, reconstructed — reference mount empty).  Uses synthetic
ImageNet-shaped data (no-egress environment); the interesting part is the
step throughput and its scaling, which synthetic data measures faithfully.

Run (simulated): ``python examples/imagenet_resnet50.py --devices 8 --steps 5
                   --batch-size 32 --image-size 64``
Run (real chip): ``python examples/imagenet_resnet50.py --steps 30
                   --batch-size 256 --bf16``
"""

import common


def main():
    args = common.parse_args(
        __doc__,
        image_size=dict(type=int, default=224),
        num_classes=dict(type=int, default=1000),
        bf16=dict(action="store_true", help="bfloat16 compute"),
        warmup=dict(type=int, default=3),
        zero=dict(action="store_true",
                  help="ZeRO-1: shard optimizer state over the mesh"),
    )
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet50
    from torchmpi_tpu.utils import data as dutil

    mpi.init(mpi.Config(dcn_size=args.dcn))
    if args.backend:
        mpi.set_config(backend=args.backend, custom_min_bytes=0)
    mesh = mpi.world_mesh()
    n_dev = mpi.device_count()
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = ResNet50(num_classes=args.num_classes, dtype=dtype)
    variables = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, args.image_size, args.image_size, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(args.lr, momentum=args.momentum)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"ResNet-50: {n_params/1e6:.1f}M params, dtype {dtype.__name__}"
          + (", ZeRO-1 sharded optimizer" if args.zero else ""))

    dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh,
                                                backend=args.backend,
                                                n_buckets=args.buckets,
                                                zero=args.zero)
    if args.zero:
        from torchmpi_tpu.parallel import zero as zero_lib

        params = mpi.nn.synchronize_parameters(params, mesh=mesh)
        batch_stats = mpi.nn.synchronize_parameters(batch_stats, mesh=mesh)
        opt_state = zero_lib.init(params, tx, mesh=mesh)  # sharded, 1/n mem
    else:
        params, opt_state, batch_stats = mpi.recipes.replicate_bn_state(
            params, tx.init(params), batch_stats, mesh=mesh)

    X, Y = dutil.synthetic_image_classification(
        max(512, args.batch_size * 2),
        image_shape=(args.image_size, args.image_size, 3),
        num_classes=args.num_classes, seed=args.seed)

    # Host batches stage onto the mesh from a background thread, so the
    # host->device copy of batch N+1 overlaps step N.
    from jax.sharding import PartitionSpec as P

    from torchmpi_tpu.utils.input_pipeline import prefetch_to_mesh

    it = prefetch_to_mesh(
        dutil.batches(X, Y, args.batch_size,
                      steps=args.steps + args.warmup, seed=args.seed),
        mesh, P(mesh.axis_names), depth=2)
    import time

    for i, (xb, yb) in enumerate(it):
        if i == args.warmup:
            jax.block_until_ready(jax.tree.leaves(params)[0])
            t0 = time.time()
        params, opt_state, batch_stats, loss = dp_step(
            params, opt_state, batch_stats, xb, yb)
        if i % 10 == 0:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    jax.block_until_ready(jax.tree.leaves(params)[0])
    dt = time.time() - t0
    imgs = args.steps * args.batch_size
    print(f"throughput {imgs/dt:.1f} img/s total, "
          f"{imgs/dt/n_dev:.1f} img/s/chip ({n_dev} devices)")
    mpi.stop()


if __name__ == "__main__":
    main()
