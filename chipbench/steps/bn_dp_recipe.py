"""The data-parallel SGD step of a model with BatchNorm statistics, through
``recipes.make_bn_dp_train_step`` and ``recipes.replicate_bn_state`` as a
user of the library calls them (library defaults, no ``Config`` field).

``build`` makes weights and a ring of device-resident batches from the
seed, each under one ``jit``, and returns what the ``train_step`` runner
drives.  The first step's loss is held to the plain reference's.
"""

import types

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import torchmpi_tpu as mpi
from chipbench import harness


def programs(cell, mesh):
    """The cell's programs, not yet run: ``init(key)`` -> the state the
    step takes, ``batches(key)`` -> the ring, ``step``.  ``build`` runs
    them; ``tools/compile_cells.py`` only takes their shapes."""
    cfg, tr = cell.config, cell.traffic
    model, tx = harness.build_model(cell), harness.build_optimizer(cell)
    batch = tr["batch_per_chip"] * mesh.devices.size
    size, chans = cfg["image_size"], cfg["channels"]

    def init(key):
        v = model.init(key, jnp.zeros((1, size, size, chans)), train=False)
        return v["params"], tx.init(v["params"]), v["batch_stats"]

    def batches(key):
        out = []
        for i in range(tr["ring"]):
            ki, kl = jax.random.split(jax.random.fold_in(key, i))
            out.append((
                jax.random.uniform(ki, (batch, size, size, chans)),
                jax.random.randint(kl, (batch,), 0, cfg["num_classes"])))
        return out

    return types.SimpleNamespace(
        init=init, batches=batches, items_per_step=batch,
        step=mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh))


def build(cell, mesh, key):
    cfg = cell.config
    prog = programs(cell, mesh)
    n = mesh.devices.size
    k_init, k_data = jax.random.split(key)
    params, opt_state, batch_stats = jax.jit(prog.init)(k_init)
    batches = jax.jit(prog.batches, out_shardings=NamedSharding(
        mesh, P(mesh.axis_names)))(k_data)

    # The plain reference, on one device, before the step donates.
    ref = harness.load_module(cell.manifest, "reference",
                              cfg["reference"]["module"])
    one = jax.devices()[0]
    ref_loss = float(jax.jit(
        lambda p, im, lb: ref.loss(p, im, lb, shards=n,
                                   stage_sizes=tuple(cfg["stage_sizes"])))(
        *jax.device_put((params, *batches[0]), one)))

    state = mpi.recipes.replicate_bn_state(params, opt_state, batch_stats,
                                           mesh=mesh)

    def check(first_loss):
        return harness.loss_check(
            "first-step loss, library step vs plain reference", first_loss,
            ref_loss, cfg["tolerance"]["loss_rtol"])

    return types.SimpleNamespace(
        step=prog.step, state=state, batches=batches, check=check,
        items_per_step=prog.items_per_step, sharded=batches[0][0],
        replicated=jax.tree.leaves(state[0])[0])
