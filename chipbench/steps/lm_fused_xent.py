"""The causal-LM train step as ``chip_smoke.phase_lm_flash_xent`` has it:
``TransformerLM`` (its ``attn_impl`` from the configuration) under
``nn.data_parallel_step``, ``nn.synchronize_gradients``, the loss through
``fused_linear_cross_entropy`` and never through ``[tokens, vocab]`` logits.

Weights, token batches and the optimizer state are made on the device from
the seed, each under one ``jit``.  Before the optimizer state exists, the
library's forward loss (flash + fused cross-entropy) on the first
``check_tokens`` tokens of the first sequence is held to the plain
reference's: dense float32 scores at that length are what one chip holds.
"""

import types

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import torchmpi_tpu as mpi
from chipbench import harness
from torchmpi_tpu.ops.xent import fused_linear_cross_entropy


def programs(cell, mesh):
    """The cell's programs, not yet run: ``init(key)`` -> the state the
    step takes, ``batches(key)`` -> the ring, ``step``.  ``build`` runs
    them; ``tools/compile_cells.py`` only takes their shapes."""
    cfg, tr = cell.config, cell.traffic
    lm, tx = harness.build_model(cell), harness.build_optimizer(cell)
    batch, seq = tr["batch_per_chip"] * mesh.devices.size, tr["seq"]
    embed = cfg["hidden_size"]
    # Init through the "local"-attention twin: attention impls share one
    # parameter tree, and init then traces no Pallas kernel.
    twin = lm.clone(attn_impl="local")

    def init_params(key):
        return twin.init(key, jnp.zeros((1, 8), jnp.int32))["params"]

    def init(key):
        params = init_params(key)
        return params, tx.init(params)

    def batches(key):
        return [(jax.random.randint(jax.random.fold_in(key, i), (batch, seq),
                                    0, cfg["vocab_size"]),)
                for i in range(tr["ring"])]

    def fused_loss(p, tok):
        h, head = lm.apply({"params": p}, tok, return_prehead=True)
        return fused_linear_cross_entropy(
            h[:, :-1].reshape(-1, embed).astype(jnp.bfloat16),
            head.astype(jnp.bfloat16), tok[:, 1:].reshape(-1)).mean()

    def step(p, o, tok):
        loss, g = jax.value_and_grad(fused_loss)(p, tok)
        g = mpi.nn.synchronize_gradients(g, mesh.axis_names)
        loss = mpi.collectives.allreduce_in_axis(loss, mesh.axis_names,
                                                 op="mean")
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    return types.SimpleNamespace(
        init=init, init_params=init_params, init_opt=tx.init,
        batches=batches, fused_loss=fused_loss, items_per_step=batch * seq,
        step=mpi.nn.data_parallel_step(step, mesh=mesh, batch_argnums=(2,)))


def build(cell, mesh, key):
    cfg = cell.config
    prog = programs(cell, mesh)
    k_init, k_data = jax.random.split(key)
    params = jax.jit(prog.init_params)(k_init)
    batches = jax.jit(prog.batches, out_shardings=NamedSharding(
        mesh, P(mesh.axis_names)))(k_data)

    # --- correctness, before the optimizer state takes its memory --------
    ref = harness.load_module(cell.manifest, "reference",
                              cfg["reference"]["module"])
    t_chk = min(cfg["reference"]["check_tokens"], cell.traffic["seq"])
    one = jax.devices()[0]
    p1, tok1 = jax.device_put((params, batches[0][0]), one)
    tok1 = tok1[:1, :t_chk]
    lib_loss = float(jax.jit(prog.fused_loss)(p1, tok1))
    ref_loss = float(jax.jit(lambda p, t: ref.loss(
        p, t, depth=cfg["num_hidden_layers"], window=cfg["sliding_window"],
        rope_base=cfg["rope_theta_as_run"],
        eps=cfg["norm_epsilon_as_run"]))(p1, tok1[0]))
    del p1, tok1
    checked = harness.loss_check(
        f"forward loss on the first {t_chk} tokens of the first sequence, "
        "flash + fused xent vs plain reference", lib_loss, ref_loss,
        cfg["tolerance"]["loss_rtol"])

    # copy=False: a device-side replication.  The default round-trips every
    # leaf through the host (2.4 GB here), and nothing reuses the template.
    params = mpi.nn.synchronize_parameters(params, mesh=mesh, copy=False)
    opt = jax.jit(prog.init_opt,
                  out_shardings=NamedSharding(mesh, P()))(params)

    return types.SimpleNamespace(
        step=prog.step, state=(params, opt), batches=batches,
        check=lambda first_loss: checked,
        items_per_step=prog.items_per_step, sharded=batches[0][0],
        replicated=jax.tree.leaves(params)[0])
