"""AlexNet asynchronous Downpour SGD — BASELINE config 4.

Reference analog: the AlexNet + ``torchmpi.parameterserver`` workload
(SURVEY.md §8.1 config 4, reconstructed — reference mount empty).  Same
Downpour structure as ``mnist_downpour.py`` with the reference's ImageNet-era
model.  Defaults are sized for the simulated CPU mesh; on real hardware raise
``--image-size 224 --num-classes 1000 --batch-size 128``.

Run: ``python examples/alexnet_downpour.py --devices 8 --workers 2``
"""

import common


def main():
    args = common.parse_args(
        __doc__,
        workers=dict(type=int, default=2),
        fetch_every=dict(type=int, default=5),
        shards=dict(type=int, default=4),
        image_size=dict(type=int, default=64),
        num_classes=dict(type=int, default=10),
        # lr: AlexNet has no normalization layers; Adam above ~1e-3 on this
        # cold start oscillates in place (loss pinned at ln C) while 3e-4
        # trains to 100% on the synthetic task (measured).
        defaults={"steps": 80, "batch_size": 32, "lr": 3e-4},
    )
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import AlexNet
    from torchmpi_tpu.utils import data as dutil

    mpi.init()
    model = AlexNet(num_classes=args.num_classes, dropout=0.0)
    params0 = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, args.image_size, args.image_size, 3)), train=False)
    ps = mpi.parameterserver.init(params0, num_shards=args.shards)

    def local_loss(p, images, labels):
        logits = model.apply(p, images, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    # Downpour with a *local* optimizer: each worker keeps its own Adam
    # state, pushes the resulting update to the PS with the 'add' rule (the
    # PS stays a dumb accumulator, exactly the reference's server-side
    # role), and periodically refetches the shared parameters.  AlexNet has
    # no normalization layers, so plain SGD barely moves from a cold start —
    # the original needed LR warmup schedules the example doesn't carry.
    tx = optax.adam(args.lr)

    @jax.jit
    def local_step(p, opt_state, images, labels):
        loss, grads = jax.value_and_grad(local_loss)(p, images, labels)
        updates, opt_state = tx.update(grads, opt_state, p)
        return updates, opt_state, loss

    devices = jax.devices()[: args.workers]
    n_workers = min(args.workers, len(devices))
    X, Y = dutil.synthetic_image_classification(
        1024, image_shape=(args.image_size, args.image_size, 3),
        num_classes=args.num_classes, seed=args.seed)
    def worker(widx):
        with jax.default_device(devices[widx]):
            params = jax.tree.map(jnp.asarray, params0)
            opt_state = tx.init(params)
            fetch_handle = None
            for step, (xb, yb) in enumerate(dutil.batches(
                    X, Y, args.batch_size, steps=args.steps,
                    seed=args.seed + widx + 1)):
                updates, opt_state, _ = local_step(
                    params, opt_state, jnp.asarray(xb), jnp.asarray(yb))
                # Push with the axpy rule scaled 1/K so the center moves by
                # the *average* of the workers' updates — K workers pushing
                # full Adam steps against near-identical params otherwise
                # move the center K-fold per round (persistent overshoot).
                ps.send(jax.tree.map(np.asarray, updates), rule="axpy",
                        alpha=1.0 / n_workers)
                params = optax.apply_updates(params, updates)
                # Prefetch at step s, adopt at s+1: the push is fully async
                # but parameter staleness stays bounded at one step — with
                # unbounded staleness the PS center (sum of all workers'
                # deltas) diverges from every worker on sharp loss surfaces
                # like AlexNet's.
                if fetch_handle is not None:
                    params = jax.tree.map(jnp.asarray, fetch_handle.wait())
                    fetch_handle = None
                elif step % args.fetch_every == 0:
                    fetch_handle = ps.receive()

    common.run_workers(worker, args.workers)

    center = jax.tree.map(jnp.asarray, ps.receive().wait())
    logits = model.apply(center, jnp.asarray(X[:256]), train=False)
    acc = float((np.argmax(np.asarray(logits), 1) == Y[:256]).mean())
    print(f"PS ops served: {ps.ops_served()}")
    print(f"final accuracy (PS params) {acc:.3f}  "
          f"(chance {1/args.num_classes:.3f})")
    ps.shutdown()
    mpi.stop()
    assert acc > 2.0 / args.num_classes, "AlexNet downpour made no progress"


if __name__ == "__main__":
    main()
