"""The main path's kernels, compiled by the CHIP's compiler at real widths.

``jax.export(..., platforms=["tpu"])`` (test_ring_lowering.py,
test_flagship_lowering.py) stops at Pallas -> Mosaic MLIR.  These tests go
on through the TPU compiler for a *described* ``v5e:2x2`` — fast-memory
limits, tiling alignment, scratch placement, HBM fit — which needs no chip
attached.  Nothing runs: a compile that passes is not a chip run
(``chip_smoke.py`` is).  The shapes are the ones ``chip_smoke.py`` and
``bench.py`` use.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, and every xdist worker imports
every test file.  Keep these tests in this one file for the same reason.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import torchmpi_tpu as mpi
from torchmpi_tpu.ops import ring

HBM_BYTES = 16 * 2 ** 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def cache_off():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture()
def chip(topo, cache_off):
    """Real Mosaic lowering (auto mode would pick the interpreter: the
    runtime's own mesh is the CPU's) for the described devices."""
    ring.set_interpret(False)
    yield topo
    ring.set_interpret(None)


@pytest.fixture()
def one_chip(chip):
    return SingleDeviceSharding(chip.devices[0])


def _mesh(chip, shape):
    return Mesh(np.asarray(chip.devices).reshape(shape), mpi.WORLD_AXES)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernels):
    """Compile ``fn`` for the described chip; it must hold at least
    ``kernels`` Mosaic kernels and fit one chip's HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < HBM_BYTES
    return compiled


# A Mosaic kernel in the compiled text: its instruction's name and the
# identity ops/ring.kernel_identity gave its pallas_call (printed over
# several lines: ``frontend_attributes={kernel_metadata={\n"tm_kernel":"x"\n}}``,
# after Pallas's own ``"mesh_axes"`` where the call sits in a shard_map).
KERNEL = re.compile(
    r'^\s*(?:ROOT )?(%\S+) = [^\n]*custom_call_target="tpu_custom_call"'
    r'[^\n]*kernel_metadata=\{[^}]*"tm_kernel":"([^"]+)"', re.M)
# How chipbench's roofline metrics find the kernels in a trace, where an
# event's name is the instruction's text: by the instruction's NAME, which
# the compiler takes from the innermost scope the call was traced in
# (chipbench/layer_metrics/{flash,xent}_roofline_pct.tok.json).
FLASH_NAME = re.compile(r"^%SPAttention_\S*$")
XENT_NAME = re.compile(r"^%(transpose_)?jvp_\S*$")


def _kernels(compiled):
    """``[(instruction name, tm_kernel identity)]`` of the Mosaic kernels."""
    found = KERNEL.findall(compiled.as_text())
    # every kernel carries an identity, and none got it as its name
    assert len(found) == compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    assert not any(ident.split(".")[0] in name for name, ident in found)
    return found


# (q heads, kv heads, seq, window, with backward, kernels expected)
FLASH_CASES = {
    "fwd_causal": (16, 16, 2048, None, False, 1),
    "fwd_gqa_window": (16, 4, 2048, 1024, False, 1),
    "grad_gqa_window": (16, 4, 2048, 1024, True, 2),   # fwd + ONE backward
    "grad_t4096": (8, 8, 4096, None, True, 2),
    # st-21b-ep4-t8k's full layer: the 4 MB resident dq block a head
    "grad_t8192_full": (28, 4, 8192, None, True, 2),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_compiles(one_chip, case):
    from torchmpi_tpu.ops.flash import flash_attention, flash_attention_grad

    H, Hkv, T, window, grad, kernels = FLASH_CASES[case]
    q = _sds((4, T, H, 128), jnp.bfloat16, one_chip)
    kv = _sds((4, T, Hkv, 128), jnp.bfloat16, one_chip)
    if grad:
        def fn(q, k, v):
            return jax.grad(lambda *a: flash_attention_grad(
                *a, causal=True, window=window).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    else:
        def fn(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window)
    compiled = _compile(fn, q, kv, kv, kernels=kernels)
    want = ["flash.dkv", "flash.fwd"] if grad else ["flash.fwd"]
    assert sorted(ident for _, ident in _kernels(compiled)) == want


# The benchmark's cells (chipbench/configs/starcoder2-3b.json): 24 q / 2 kv
# heads of 128 over hidden 3072, window 4096; one sequence of 8192 (the
# window binds) and eight of 1024 (it never does).  (batch, seq)
SC2_CASES = {"sc2_3b_t8k": (1, 8192), "sc2_3b_t1k": (8, 1024)}


@pytest.mark.parametrize("case", sorted(SC2_CASES))
def test_flash_compiles_at_the_cells_shapes_under_its_module(one_chip, case):
    # Through the model's own layer, as the cells run it: the kernels'
    # instructions are then named after the flax module (SPAttention_0),
    # which flash_roofline_pct.tok's pattern leans on, and fused xent's
    # pattern must not find them.
    from torchmpi_tpu.models.transformer import Block

    batch, seq = SC2_CASES[case]
    block = Block(24, 128, attn_impl="flash", dtype=jnp.bfloat16,
                  window=4096, num_kv_heads=2, rope=True)
    x = _sds((batch, seq, 3072), jnp.bfloat16, one_chip)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x))

    def fn(params, x):
        return jax.grad(lambda p: block.apply(p, x).astype(
            jnp.float32).sum())(params)

    found = _kernels(_compile(fn, params, x, kernels=2))
    assert sorted(ident for _, ident in found) == ["flash.dkv", "flash.fwd"]
    assert all(FLASH_NAME.match(name) and not XENT_NAME.match(name)
               for name, _ in found), found


@pytest.mark.parametrize("tokens,embed,vocab", [
    (8188, 2048, 32768), (32768, 1024, 32768), (8191, 3072, 49152),
    (8184, 3072, 49152), (100, 512, 1000)])
def test_fused_xent_value_and_grad_compiles(one_chip, tokens, embed, vocab):
    # 8188 x 2048 is the flagship LM step's head (B=4, T=2048, minus the
    # shifted token); 32768 x 1024 is the LM-head scale of the export test;
    # 8191 and 8184 x 3072 x 49152 are the heads of the benchmark's
    # sc2-3b-t8k and sc2-3b-t1k (eight sequences, each minus one token);
    # 100 rows are fewer than a block and off the sublane tiling of 8, which
    # the backward's DMA of its dx rows must not meet.
    from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

    def fn(x, w, labels):
        return jax.value_and_grad(lambda x, w: fused_linear_cross_entropy(
            x, w, labels).mean(), argnums=(0, 1))(x, w)

    compiled = _compile(fn, _sds((tokens, embed), jnp.bfloat16, one_chip),
                        _sds((embed, vocab), jnp.bfloat16, one_chip),
                        _sds((tokens,), jnp.int32, one_chip),
                        kernels=2)
    found = _kernels(compiled)
    # the forward and ONE backward, which carries xent.dw's identity
    assert sorted(ident for _, ident in found) == ["xent.dw", "xent.fwd"]
    # outside any module the custom_vjp's empty scope names them: what
    # xent_roofline_pct.tok's pattern finds, and flash's does not
    assert all(XENT_NAME.match(name) and not FLASH_NAME.match(name)
               for name, _ in found), found


@pytest.mark.parametrize("call", ["gather", "gather_weighted", "combine"])
def test_expert_row_kernels_compile_at_the_cells_shapes(one_chip, call):
    # st-21b-ep4-t8k's expert layer: 8192 tokens of 2560 bf16, six routes
    # a token.  The side indexed at random is resident in VMEM whole (the
    # 42 MB token table; the 84 MB of float32 sums), which only the chip's
    # compiler can refuse.
    from torchmpi_tpu.ops import moe

    tokens, width, routes = 8192, 2560, 6 * 8192
    assert moe.block_rows(routes) == 256
    assert moe.token_parts(tokens, width) == 1
    u = _sds((tokens, width), jnp.bfloat16, one_chip)
    y = _sds((routes, width), jnp.float32, one_chip)    # the down product
    w = _sds((routes,), jnp.float32, one_chip)
    order = _sds((routes,), jnp.int32, one_chip)
    n_live = _sds((), jnp.int32, one_chip)
    if call == "gather":
        compiled = _compile(moe.rows_from_tokens, u, order, n_live,
                            kernels=1)
    elif call == "gather_weighted":
        compiled = _compile(
            lambda u, o, n, w, y: moe.rows_from_tokens(u, o, n, weight=w,
                                                       against=y),
            u, order, n_live, w, y, kernels=1)
    else:
        compiled = _compile(
            lambda y, o, n, w: moe.tokens_from_rows(
                y, o, n, tokens, weight=w, round_to=jnp.bfloat16),
            y, order, n_live, w, kernels=1)
    ((name, ident),) = _kernels(compiled)
    assert ident == ("moe.combine" if call == "combine" else "moe.gather")
    assert not XENT_NAME.match(name) and not FLASH_NAME.match(name)


@pytest.mark.parametrize("slots", [5, 20])
@pytest.mark.parametrize("call", ["gather", "combine"])
def test_expert_row_kernels_compile_at_a_decode_steps_rows(one_chip, call,
                                                           slots):
    # imoe-16b-serve-conv-sat's pooled decode step: a row a slot of 2048
    # bf16, six routes each.  The buffer is smaller than one block of 256,
    # so the block is the buffer: 120 rows, or 30, which is no multiple of
    # the 8-row tile.
    from torchmpi_tpu.ops import moe

    width, routes = 2048, 6 * slots
    assert moe.block_rows(routes) == routes
    order = _sds((routes,), jnp.int32, one_chip)
    n_live = _sds((), jnp.int32, one_chip)
    if call == "gather":
        compiled = _compile(moe.rows_from_tokens,
                            _sds((slots, width), jnp.bfloat16, one_chip),
                            order, n_live, kernels=1)
    else:
        compiled = _compile(
            lambda y, o, n, w: moe.tokens_from_rows(
                y, o, n, slots, weight=w, round_to=jnp.bfloat16),
            _sds((routes, width), jnp.float32, one_chip), order, n_live,
            _sds((routes,), jnp.float32, one_chip), kernels=1)
    ((_, ident),) = _kernels(compiled)
    assert ident == ("moe.combine" if call == "combine" else "moe.gather")


def _harness():
    """``chipbench/harness.py``: the benchmark's files sit beside ``tests/``."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from chipbench import harness

    return harness


def _served_starcoder2(one_chip, layers=None):
    """``starcoder2-3b-serve`` as the served cells build it (its widths, its
    slots, bfloat16 weights; ``layers`` cuts the depth), as shapes on the
    described chip: -> (decode model, params, slots, sampling operands)."""
    return _served(one_chip, "sc2-3b-serve-sat", layers)


def _served(one_chip, workload, layers=None):
    harness = _harness()
    cell = harness.resolve(harness.load_manifest(), workload)
    if layers is not None:
        cell.config["num_hidden_layers"] = layers
    model = harness.build_model(cell)
    srv = cell.config["serving"]
    dmodel = model.clone(decode=True, max_len=srv["slot_tokens"])
    params = jax.tree.map(
        lambda a: _sds(a.shape, jnp.bfloat16, one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])

    def sampling(rows):
        return tuple(_sds((rows,), dt, one_chip) for dt in (
            jnp.uint32, jnp.int32, jnp.float32, jnp.int32, jnp.float32))

    return dmodel, params, srv["slots"], sampling


@pytest.mark.parametrize("bucket", [64, 4096])
def test_served_prefill_compiles_with_the_flash_kernel(one_chip, bucket):
    # sc2-3b-serve-*'s prefill program at the smallest bucket (less than one
    # block of the kernel: q and k padded inside it) and the largest, two
    # layers of the thirty: 24 q / 2 kv heads of 128 in float32, window
    # 4096.  On the chip a prompt's attention is ops/flash's forward kernel
    # (models/transformer.prefill_runs_flash), and no [heads, T, T] array is
    # left in the program.
    from torchmpi_tpu.models.generate import _slot_prefill_jit

    layers = 2
    dmodel, params, _, sampling = _served_starcoder2(one_chip, layers)
    compiled = _slot_prefill_jit.lower(
        dmodel, params, _sds((1, bucket), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), *sampling(1)).compile()
    found = _kernels(compiled)
    assert [ident for _, ident in found] == ["flash.fwd"] * layers
    # traced once and called by every layer (transformer._prompt_attention),
    # so named after that call and not after the layer: neither train
    # roofline metric's pattern finds them
    assert not any(FLASH_NAME.match(name) or XENT_NAME.match(name)
                   for name, _ in found), found
    assert not re.search(r"\[(?:\d+,)+%d,%d\]" % (bucket, bucket),
                         compiled.as_text())
    ma = compiled.memory_analysis()
    # what the dense form needs for its scores alone at 4096: 1.6 GB
    assert ma.temp_size_in_bytes < 0.6e9


def test_served_decode_step_lowers_to_the_parents_text(one_chip):
    # jit__slot_step_jit of starcoder2-3b-serve (30 layers, 5 slots of
    # 4608) lowers for the described chip to a pinned text (sha256 as
    # PERF.md section 6 has it).  PR 32 and PR 33 left it at 132fe595...;
    # PR 34 meant to touch it and did: the pool is donated (every cache leaf
    # of the entry carries tf.aliasing_output), nothing else: a179e72a...;
    # PR 36 meant to touch it and did: the sampling tail is one `case` of
    # two regions under the scope `sample_rows`, nothing else.  A change
    # that means to touch the step replaces the digest and says so there.
    import hashlib

    from torchmpi_tpu.models.generate import _slot_step_jit

    dmodel, params, slots, sampling = _served_starcoder2(one_chip)
    cache = _pool_cache(dmodel, slots, one_chip)
    text = _slot_step_jit.lower(
        dmodel, params, cache, _sds((slots,), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip), *sampling(slots)).as_text()
    assert "tpu_custom_call" not in text
    assert text.count("tf.aliasing_output") == len(jax.tree.leaves(cache))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "f6b412bd3e16ffcf64999b7cc5319637e855fbd9152a4cdcd1a6bea808b21133")


def _pool_cache(dmodel, slots, one_chip):
    return jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: dmodel.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
            pos_offset=jnp.zeros((slots,), jnp.int32)))["cache"])


@pytest.fixture(scope="module")
def compiled_steps():
    """The served cells' pooled steps as compiled for the described chip, by
    cell: a step of a whole model takes half a minute, and two tests read
    one."""
    return {}


def _compiled_step(steps, one_chip, workload):
    """``jit__slot_step_jit`` of a served cell at its file's slots ->
    (the pool's shapes, the executable), compiled once a module."""
    from torchmpi_tpu.models.generate import _slot_step_jit

    if workload not in steps:
        dmodel, params, slots, sampling = _served(one_chip, workload)
        cache = _pool_cache(dmodel, slots, one_chip)
        steps[workload] = cache, _slot_step_jit.lower(
            dmodel, params, cache, _sds((slots,), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip), *sampling(slots)).compile()
    return steps[workload]


@pytest.mark.parametrize("workload", [
    "sc2-3b-serve-sat", "imoe-16b-serve-conv-sat",
    "nm3s-120b-serve-chat-sat", "jamba2-3b-serve-reason-sat"])
def test_served_decode_step_updates_the_pool_in_place(
        one_chip, compiled_steps, workload):
    # The pooled step of each served configuration at its file's slots: the
    # pool is DONATED (PR 34), so the executable aliases every byte of it
    # to its result, and no instruction copies a whole leaf with a token
    # axis to a leaf of the same type: the parent's text held 2 such copies
    # (starcoder2: f32[5,4608,2,128]), 9 (instella: f32[21,3072,512], 132 MB
    # each, 3.6 ms of a 28.3 ms step on the chip) and 2 (nemotron:
    # f32[68,2048,2,128]).  What stays and is not the pool's copy: the
    # attention's READ of a leaf as bfloat16 in another layout (a `copy` to
    # bf16), the compiler's own round trip of a leaf through fast memory
    # around the per-row write (`copy-start`: 58 of starcoder2's 60 leaves
    # at the parent, 44 now), and the convolution taps, [slots, 3, 10240],
    # which shift by one and so cannot be written over what is still read.
    from torchmpi_tpu.models.generate import STATE_LEAVES

    cache, compiled = _compiled_step(compiled_steps, one_chip, workload)
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    pool_bytes = sum(a.size * a.dtype.itemsize for _, a in leaves)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes > 1.2e9
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < 1e6
    names = {"float32": "f32", "bfloat16": "bf16"}
    token_leaves = {
        "%s[%s]" % (names[a.dtype.name], ",".join(map(str, a.shape)))
        for path, a in leaves
        if a.ndim and path[-1].key not in STATE_LEAVES}
    assert token_leaves
    copied = re.findall(r"^\s*(?:ROOT )?%copy[\w.]* = (\w+\[[\d,]*\])\S* "
                        r"copy\(", compiled.as_text(), re.M)
    assert copied and not token_leaves & set(copied)


@pytest.mark.parametrize("workload", [
    "sc2-3b-serve-sat", "imoe-16b-serve-conv-sat",
    "nm3s-120b-serve-chat-sat", "jamba2-3b-serve-reason-sat"])
def test_served_decode_step_sorts_the_vocabulary_in_one_branch_only(
        one_chip, compiled_steps, workload):
    # The sampling tail of each served configuration's pooled step, as the
    # chip's compiler leaves it (PR 36): ONE `conditional` under the scope
    # `sample_rows` with two branch computations (a select, both sides
    # run, would leave none), the first an argmax with no `sort` and no
    # random bits, and every sort over [slots, vocab] of the whole program
    # in the second.  The parent's text held that sort in the entry
    # computation: 3.6 ms of a 24.8 ms step at [21, 128896].
    harness = _harness()
    cell = harness.resolve(harness.load_manifest(), workload)
    rows = "[%d,%d]" % (cell.config["serving"]["slots"],
                        cell.config["vocab_size"])
    text = _compiled_step(compiled_steps, one_chip, workload)[1].as_text()
    bodies = dict(re.findall(
        r"^(?:ENTRY )?(%[\w.\-]+) \(.*?\{\n(.*?)^\}", text, re.M | re.S))
    (tail,) = re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}.*"
        r"op_name=\"[^\"]*/sample_rows/", text)
    greedy, drawn = (bodies[b] for b in tail.split(", "))
    assert "branch_0_fun/reduce" in greedy          # the argmax
    assert " sort(" not in greedy and "rng-bit-generator" not in greedy
    assert "_gumbel" not in greedy and "_gumbel" in drawn
    sorts = {name: len(re.findall(r"%s\S* sort\(" % re.escape(rows), body))
             for name, body in bodies.items()}
    assert sorts.pop(tail.split(", ")[1]) == 1, sorts
    assert not any(sorts.values()), sorts


@pytest.mark.parametrize("program", ["prefill64", "prefill1024", "step"])
def test_served_hybrid_compiles_at_the_cells_sizes(
        one_chip, compiled_steps, program):
    # nm3s-120b-serve-chat-sat's programs at the published widths
    # (chipbench/configs/nemotron3-super-120b-a12b-serve.json): 5 Mamba-2
    # mixers of 128 heads x 64 with a state of 128 (a prompt in chunks of
    # 128, a pooled step ONE recurrent update a slot), 5 latent expert
    # layers with 128 of 512 relu2 experts held, top-22, one GQA layer whose
    # prompt runs the flash forward kernel; 9.3 GB of bfloat16 weights.  The
    # smallest and the largest prefill bucket of the cell's traffic and the
    # pooled step at the file's slots fit the chip; the step's cache has the
    # two state leaves a mixer and no token axis on them.
    from torchmpi_tpu.models.generate import STATE_LEAVES, _slot_prefill_jit

    if program == "step":
        cache, compiled = _compiled_step(compiled_steps, one_chip,
                                         "nm3s-120b-serve-chat-sat")
        state = [a for path, a in jax.tree_util.tree_leaves_with_path(cache)
                 if path[-1].key in STATE_LEAVES]
        slots = state[0].shape[0]
        assert sorted(a.shape for a in state) == sorted(
            [(slots, 128, 64, 128), (slots, 3, 10240)] * 5)
        attention = []
    else:
        dmodel, params, _, sampling = _served(one_chip,
                                              "nm3s-120b-serve-chat-sat")
        bucket = int(program[len("prefill"):])
        compiled = _slot_prefill_jit.lower(
            dmodel, params, _sds((1, bucket), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip), *sampling(1)).compile()
        attention = ["flash.fwd"]
    # the library's kernels: the one attention layer's prompt, and the live
    # rows' gather and combine of the five expert layers; the compiler's
    # own grouped-matmul kernels for the experts' TWO products a layer
    # (1,408 sorted rows a step over 128 experts: grouped, not dense)
    text = compiled.as_text()
    assert sorted(ident for _, ident in KERNEL.findall(text)) == sorted(
        attention + ["moe.gather", "moe.combine"] * 5)
    assert len(re.findall(r"^\s*(?:ROOT )?%ragged-dot-none\S* = ", text,
                          re.M)) == 2 * 5
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < 16_909_336_064


@pytest.mark.parametrize("program", ["prefill64", "prefill1024", "step"])
def test_served_jamba_compiles_at_the_cells_sizes(
        one_chip, compiled_steps, program):
    # jamba2-3b-serve-reason-sat's programs at the published widths
    # (chipbench/configs/jamba2-3b-serve.json), the WHOLE model: 26 Mamba-1
    # mixers of 5120 channels x 16 states (a prompt's scan in pieces, a
    # pooled step ONE recurrent update a slot), two attention layers with
    # one key/value head whose prompt runs the flash forward kernel, 28
    # SwiGLU feed-forwards of 8192, a tied head: 3,029,337,472 parameters,
    # 6.06 GB in bfloat16 and no `head` among them.  The smallest and the
    # largest prefill bucket of the cell's traffic and the pooled step at
    # the file's slots fit the chip; the step's cache has the two state
    # leaves a mixer, channels-minor, no token axis on them; a slot's bytes
    # are the configuration's count; and a 1024-token prompt's scan holds
    # nothing of [T, 16, 5120] (335 MB a layer): the prefill's temporaries
    # stay under 0.6 GB.
    from chipbench import flops_jamba_serve as counts
    from torchmpi_tpu.models.generate import STATE_LEAVES, _slot_prefill_jit

    harness = _harness()
    workload = "jamba2-3b-serve-reason-sat"
    cfg = harness.resolve(harness.load_manifest(), workload).config
    sizes = {k: cfg[k] for k in cfg["flops"]["sizes"]}
    dmodel, params, slots, sampling = _served(one_chip, workload)
    assert "head" not in params
    assert sum(a.size for a in jax.tree.leaves(params)) == counts.parameters(
        **sizes) == 3_029_337_472
    if program == "step":
        cache, compiled = _compiled_step(compiled_steps, one_chip, workload)
        leaves = jax.tree_util.tree_leaves_with_path(cache)
        state = [a for path, a in leaves if path[-1].key in STATE_LEAVES]
        assert sorted(a.shape for a in state) == sorted(
            [(slots, 16, 5120), (slots, 3, 5120)] * 26)
        assert sum(a.size * a.dtype.itemsize for a in state) == (
            slots * counts.state_bytes_per_slot(**sizes)) == (
                slots * 10_117_120)
        tokens = [a for path, a in leaves
                  if a.ndim and path[-1].key not in STATE_LEAVES]
        assert sorted(a.shape for a in tokens) == [
            (slots, cfg["serving"]["slot_tokens"], 1, 128)] * 4
        attention, limit = [], 1.0e9
    else:
        bucket = int(program[len("prefill"):])
        compiled = _slot_prefill_jit.lower(
            dmodel, params, _sds((1, bucket), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip), *sampling(1)).compile()
        attention, limit = ["flash.fwd"] * 2, 0.6e9
    text = compiled.as_text()
    assert sorted(ident for _, ident in KERNEL.findall(text)) == attention
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < limit
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < 15e9


def test_smallthinker_step_compiles_at_the_cells_sizes(chip):
    # The whole train step of the benchmark's st-21b-ep4-t8k
    # (chipbench/configs/smallthinker-21b-a3b.json) at its real sizes, as
    # chipbench/tools/compile_cells.py builds it: 28 q / 4 kv heads of 128
    # with and without the 4096 window in one program, 16 of 64 experts held
    # dropless (lax.ragged_dot, which the chip's compiler turns into
    # grouped-matmul kernels of its own), and a 37984-row vocabulary slice,
    # no multiple of fused xent's tile.  It must fit what the chip's
    # allocator gives (bytes_limit 16,909,336,064 on a v5e; PERF.md).
    harness = _harness()
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, "st-21b-ep4-t8k")
    mesh = Mesh(np.asarray(chip.devices[:1]).reshape((1, 1)), mpi.WORLD_AXES)
    prog = harness.load_module(manifest, "steps",
                               cell.config["step"]).programs(cell, mesh)
    key = jax.ShapeDtypeStruct((2,), np.uint32)

    def put(spec):
        return lambda s: _sds(s.shape, s.dtype, NamedSharding(mesh, spec))

    state = jax.tree.map(put(P()), jax.eval_shape(prog.init, key))
    batch = jax.tree.map(put(P(mesh.axis_names)),
                         jax.eval_shape(prog.batches, key)[0])
    compiled = prog.step.jitted.lower(*state, *batch).compile()
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < 16_909_336_064
    text = compiled.as_text()
    found = KERNEL.findall(text)
    layers = cell.config["num_hidden_layers"]
    # every Pallas kernel of the library carries its identity ...
    idents = [ident for _, ident in found]
    assert sorted(set(idents)) == ["flash.dkv", "flash.fwd", "moe.combine",
                                   "moe.gather", "xent.dw", "xent.fwd"]
    # ... the forward and ONE backward (flash.dkv, which writes dq too) a
    # layer, the full layer's with its 4 MB dq block resident ...
    assert all(idents.count(f"flash.{k}") == layers for k in ("fwd", "dkv"))
    assert all(FLASH_NAME.match(name) for name, ident in found
               if ident.startswith("flash."))
    # the expert layer's rows, a layer: gathered forward, again in the
    # recomputation and as the combine's transpose; combined forward and
    # as the dispatch's transpose.  Named after the scope they run in, so
    # that neither roofline metric counts them as its own.
    assert idents.count("moe.gather") == 3 * layers
    assert idents.count("moe.combine") == 2 * layers
    moved = [name for name, ident in found if ident.startswith("moe.")]
    assert not any(XENT_NAME.match(n) or FLASH_NAME.match(n) for n in moved)
    # ... and the rest of the Mosaic calls are the compiler's grouped
    # matmuls: gate, up and down forward, again in the backward pass's
    # recomputation, and two products each backward, in every layer
    grouped = re.findall(r"^\s*(?:ROOT )?%(ragged-dot-none\S*) = ", text,
                         re.M)
    assert len(grouped) == 12 * layers
    assert text.count('custom_call_target="tpu_custom_call"') == len(
        found) + len(grouped) + len(re.findall(
            r"^\s*(?:ROOT )?%ragged-dot-metadata\S* = ", text, re.M))


def _rank_major_program(mesh, body):
    spec = P(mesh.axis_names)
    return shard_map(lambda xs: body(xs[0], mesh.axis_names)[None],
                     mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)


MIB64 = 16 * 1024 * 1024  # f32 elements per rank: chip_smoke's large message

# (verb, f32 elements per rank, Config overrides, tm_kernel identities)
RING_CASES = {
    "allreduce_resident_256k": ("allreduce", 65536, {},
                                {"ring.allreduce.padded"}),
    "allreduce_bidirectional": ("allreduce", 65536,
                                {"pallas_bidirectional": True},
                                {"ring.allreduce.bidir_padded"}),
    "allreduce_chunked_64m": ("allreduce", MIB64, {},
                              {"ring.allreduce.chunked"}),
    "all_gather_chunked_16m": ("all_gather", MIB64 // 4, {},
                               {"ring.all_gather.chunked"}),
    "reduce_scatter_chunked_64m": ("reduce_scatter", MIB64, {},
                                   {"ring.reduce_scatter.chunked"}),
    "reduce_scatter_all_gather_resident": (
        "rs_ag", 4096, {}, {"ring.reduce_scatter", "ring.all_gather"}),
}


@pytest.mark.xfail(strict=True, reason=(
    "found by PR 24, open: with pallas_bidirectional the streaming "
    "allreduce keeps four double-buffered VMEM slots, 23.81 MB against "
    "Mosaic's 16 MB scoped limit at the default chunk_bytes"))
def test_bidirectional_chunked_allreduce_compiles(chip, flat_runtime):
    mpi.set_config(custom_min_bytes=0, pallas_bidirectional=True)
    mesh = _mesh(chip, (1, 4))
    _compile(_rank_major_program(mesh, ring.ring_allreduce),
             _sds((4, MIB64), jnp.float32,
                  NamedSharding(mesh, P(mesh.axis_names))), kernels=1)


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_ring_verbs_compile_on_four_chips(chip, flat_runtime, case):
    verb, m, overrides, identities = RING_CASES[case]
    mpi.set_config(custom_min_bytes=0, **overrides)
    mesh = _mesh(chip, (1, 4))
    if verb == "rs_ag":
        def body(x, axes):
            return ring.ring_all_gather(
                ring.ring_reduce_scatter(x, axes), axes).reshape(-1)
    else:
        body = {"allreduce": ring.ring_allreduce,
                "reduce_scatter": ring.ring_reduce_scatter,
                "all_gather": ring.ring_all_gather}[verb]
    if m == MIB64:  # the streaming kernel, not the VMEM-resident one
        assert ring._effective_plan(m, 4, np.float32,
                                    ring.runtime_chunk_bytes(),
                                    interpreted=False)[1] > 1
    compiled = _compile(
        _rank_major_program(mesh, body),
        _sds((4, m), jnp.float32, NamedSharding(mesh, P(mesh.axis_names))),
        kernels=2 if verb == "rs_ag" else 1)
    assert {ident for _, ident in _kernels(compiled)} == identities


def test_two_level_allreduce_compiles_on_2x2(chip, hier_runtime):
    # The hierarchical backend is XLA collectives staged over two axes:
    # no Mosaic kernel is expected, the 2x2 partitioning is what is tried.
    from torchmpi_tpu.parallel.hierarchical import hier_allreduce

    mesh = _mesh(chip, (2, 2))
    compiled = _compile(
        _rank_major_program(mesh, hier_allreduce),
        _sds((4, MIB64), jnp.float32, NamedSharding(mesh,
                                                    P(mesh.axis_names))),
        kernels=0)
    text = compiled.as_text()
    assert "reduce-scatter" in text or "all-reduce" in text
