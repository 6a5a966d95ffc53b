"""The main path's kernels and steps, compiled for a v5e that is described and
not attached (on-chip-measurement guide, section 2.3) at the widths
`chip_smoke.py` runs: what the chip's compiler would refuse — a block that
does not tile, too much VMEM, a program that does not fit 16 GB — fails here,
at no chip time. A compile that passes is not a chip run.

Everything that touches the topology lives in module-scoped fixtures of this
one file: only the worker that is handed this file loads the TPU compiler,
and it compiles in its own process. The code under test picks interpret mode
from `jax.default_backend()`, which is the CPU here, so the tests pass
`interpret=False` (kernels) or steer `_interpret_default` (whole steps).
"""
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.kernels.flash_attention import (flash_attention,
                                                        flash_attention_lse,
                                                        flash_decode,
                                                        flash_decode_append,
                                                        flash_decode_paged,
                                                        kv_append)

# the module, not the function of the same name the package re-exports
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
mla = importlib.import_module("deeplearning4j_tpu.kernels.mla_decode")
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_config():
    """The process as it is on the chip machine, for this module only: no
    x64 (tests/conftest.py turns it on for the CPU's gradient checks; under
    it the kernels' Python constants trace as f64, which Mosaic refuses and
    no TPU run ever sees), and no persistent compilation cache (a compile for
    a described chip is written to it but cannot be read back without the
    chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    cc.reset_cache()


@pytest.fixture
def on_chip(one_chip, chip_config):
    """shape, dtype -> a ShapeDtypeStruct placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def mla_row_tiles(fn, *args):
    """How many [256, 640] bfloat16 row tiles `mla_decode` holds in VMEM,
    each with a DMA semaphore of its own: one more than the block copies it
    keeps in flight."""
    traced = str(jax.make_jaxpr(fn)(*args))
    tiles, = set(re.findall(r"Ref<vmem>\{bf16\[(\d+),256,640\]\}",
                            traced))
    assert f"dma_sem[{tiles}]" in traced
    return int(tiles)


_RESULT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def _elements(dims):
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def relayouts(text, elements):
    """Names of the instructions of an optimized HLO text that rewrite an
    array of at least `elements` elements into another layout: a `copy`, a
    `transpose`, or a fusion whose root is one. (A bitcast moves nothing, an
    in-place `dynamic-update-slice` is the cache's own append, and a
    `copy-start` / `copy-done` pair changes the memory space, not the
    layout.)"""
    roots, rows = {}, []
    computation = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(2)
        m = _RESULT.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        if line.lstrip().startswith("ROOT"):
            roots[computation] = op
        rows.append((name, _elements(dims), op, _CALLS.search(line)))
    moved = ("copy", "transpose")
    return [name for name, n, op, calls in rows if n >= elements and (
        op in moved or (op == "fusion" and calls
                        and roots.get(calls.group(1)) in moved))]


COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|"
                        r"collective-permute")


def loops(text):
    """The `op_name` of every `while` loop of an optimized HLO text."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines() if re.search(r" while\(", line)]


def under_scope(text, scope, op, elements):
    """Names of the instructions of an optimized HLO text of at least
    `elements` elements whose `op_name` passes through `scope` and ends in
    `op` (the jax primitive: `select_n`, `gather`, ...; "": any)."""
    found = []
    for line in text.splitlines():
        m, name = _RESULT.match(line), re.search(r'op_name="([^"]*)"', line)
        if m and name and f"/{scope}/" in name.group(1) \
                and name.group(1).endswith(op) \
                and _elements(m.group(2)) >= elements:
            found.append(m.group(1))
    return found


def operand_copies(text, elements):
    """The `relayouts` of an expert layer's operand (`elements` of it): what
    its dispatch or its grouped product copies or transposes, less the
    `transpose` of dimensions {0,1} XLA fuses INTO the gather, which moves
    nothing."""
    ours = set(under_scope(text, "moe_dispatch", "", elements)
               + under_scope(text, "moe_experts", "", elements))
    return sorted(set(relayouts(text, elements)) & ours
                  - set(under_scope(text, "moe_dispatch", "gather", elements)))


_CALLEE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_SORT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* sort\(")


def unconditional_sorts(text):
    """Names of the `sort` instructions of an optimized HLO text that run
    whenever the program does: those in the entry computation or in one it
    reaches by a call, a fusion, a loop or a reduction. What only a branch
    of a `conditional` reaches (`branch_computations`, `true_computation`,
    `false_computation`) is not followed."""
    bodies, entry, computation = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(2)
            bodies[computation] = []
            if head.group(1):
                entry = computation
        elif computation is not None:
            bodies[computation].append(line)
    reached, todo = set(), [entry]
    while todo:
        computation = todo.pop()
        if computation in reached:
            continue
        reached.add(computation)
        for line in bodies[computation]:
            todo.extend(_CALLEE.findall(line))
    return [m.group(1) for computation in sorted(reached)
            for m in map(_SORT.match, bodies[computation]) if m]


def sorts_only_under_a_conditional(text):
    """The sampler's sort is in the program, and only a branch of a
    `conditional` reaches it: a greedy batch runs none."""
    return " sort(" in text and " conditional(" in text \
        and unconditional_sorts(text) == []


def compiled_append(kv, new, pos, **how):
    """`kv_append` of one token into two donated caches, compiled."""
    return jax.jit(
        lambda k, v, kn, vn, pos: kv_append(k, v, kn, vn, pos, **how),
        donate_argnums=(0, 1)).lower(kv, kv, new, new, pos).compile()


def graded(attend):
    def loss(q, k, v, *rest):
        out = attend(q, k, v, *rest)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))


# [batch, time, heads, head_dim]: the transformer_lm train step of
# chip_smoke.py, its kernel check, and the widest shape the issue's author
# compiled
SHAPES = [(16, 512, 4, 64), (4, 4096, 8, 64), (2, 4096, 16, 128)]
DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_flash_forward_compiles(on_chip, shape, dtype):
    q = on_chip(shape, dtype)
    text = compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, q, q)
    assert text.count(KERNEL) == 1


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_flash_forward_backward_causal_compiles(on_chip, shape, dtype):
    q = on_chip(shape, dtype)
    text = compiled_text(graded(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)), q, q, q)
    assert text.count(KERNEL) == 3          # forward, dQ, dK/dV


@pytest.mark.parametrize("shape", SHAPES[:2], ids=str)
def test_flash_forward_backward_masked_compiles(on_chip, shape):
    q = on_chip(shape, jnp.bfloat16)
    mask = on_chip(shape[:2], jnp.float32)
    text = compiled_text(graded(
        lambda q, k, v, m: flash_attention(q, k, v, causal=True, key_mask=m,
                                           interpret=False)), q, q, q, mask)
    assert text.count(KERNEL) == 3


@pytest.mark.parametrize("shape", SHAPES[:2], ids=str)
def test_flash_lse_offsets_forward_backward_compiles(on_chip, shape):
    """The ring-attention variant: (out, lse) primal pair with dynamic
    global q/k offsets riding in SMEM."""
    q = on_chip(shape, jnp.bfloat16)
    off = on_chip((), jnp.int32)

    def attend(q, k, v, qo, ko):
        return flash_attention_lse(q, k, v, causal=True, q_offset=qo,
                                   k_offset=ko, interpret=False)
    text = compiled_text(graded(attend), q, q, q, off, off)
    assert text.count(KERNEL) == 3


# the decode shapes of chip_smoke.py's server: 8 slots, capacity 256,
# 4 heads of 64; and a long cache
@pytest.mark.parametrize("capacity", [256, 4096])
def test_flash_decode_compiles(on_chip, capacity):
    q = on_chip((8, 1, 4, 64), jnp.bfloat16)
    kv = on_chip((8, capacity, 4, 64), jnp.bfloat16)
    lengths = on_chip((8,), jnp.int32)
    text = compiled_text(
        lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
        q, kv, kv, lengths)
    assert text.count(KERNEL) == 1
    assert relayouts(text, 8 * capacity * 4 * 64) == []


def test_flash_decode_reads_the_cache_where_it_lies(on_chip):
    """The decode attention of the `opt350m_batch_decode` cell: 48 slots of
    1024 positions, 16 heads of 64, float32. The TPU stores such a buffer
    with the positions minor-most, which is the kernel's operand layout: the
    compiled call is the kernel and bitcasts, and no instruction copies or
    transposes an array the size of a K or V slab. (Through the training
    forward this was two transposing copies of 201 MB a layer: 46 ms of a
    96 ms decode step on the chip.)"""
    S, C, H, D = 48, 1024, 16, 64
    q = on_chip((S, 1, H, D), jnp.float32)
    kv = on_chip((S, C, H, D), jnp.float32)
    comp = jax.jit(lambda q, k, v, n: flash_decode(q, k, v, n,
                                                   interpret=False)).lower(
        q, kv, kv, on_chip((S,), jnp.int32)).compile()
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert relayouts(text, S * C * H * D) == []
    assert comp.memory_analysis().temp_size_in_bytes < 1 << 20


def test_flash_decode_runs_per_shard_on_a_mesh(topo, chip_config):
    """`ServingServer(mesh=4)`: the cache head-sharded over four chips. The
    kernel runs per shard inside `_per_shard`'s shard_map on 4 of the 16
    heads, still on the buffer as it lies: one kernel, no slab rewritten,
    no collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (DATA_AXIS, MODEL_AXIS))
    S, C, H, D = 48, 1024, 16, 64
    heads = NamedSharding(mesh, P(None, None, MODEL_AXIS, None))
    q = jax.ShapeDtypeStruct((S, 1, H, D), jnp.float32, sharding=heads)
    kv = jax.ShapeDtypeStruct((S, C, H, D), jnp.float32, sharding=heads)
    lengths = jax.ShapeDtypeStruct((S,), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        text = compiled_text(
            lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
            q, kv, kv, lengths)
    assert text.count(KERNEL) == 1
    assert relayouts(text, S * C * H * D // 4) == []
    assert not COLLECTIVE.search(text)


def test_kv_append_writes_the_cache_in_place(on_chip):
    """The append of the `opt350m_batch_decode` cell: the new token's K and
    V of 48 slots into two donated 48 x 1024 x 16 x 64 float32 buffers. One
    kernel on the buffers as they lie (the transposes around it are
    bitcasts), its outputs aliased onto the donated inputs, no loop over
    the slots and nothing the size of a slab copied or allocated. (As a
    vmapped `dynamic_update_slice` this was 2 `while` loops of 48 strided
    updates a layer: 19 ms of a 36.6 ms decode step on the chip.)"""
    S, C, H, D = 48, 1024, 16, 64
    kv, new = on_chip((S, C, H, D), jnp.float32), on_chip((S, 1, H, D),
                                                          jnp.float32)
    comp = compiled_append(kv, new, on_chip((S,), jnp.int32),
                           interpret=False)
    text = comp.as_text()
    assert text.count(KERNEL) == 1 and "%kv_append" in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 4
    # the kernel's outputs are its slab operands (3 and 4, after the
    # positions and the two new rows), and the program's are its arguments
    assert re.search(r"output_to_operand_aliasing=\{\{0\}: \(3, \{\}\), "
                     r"\{1\}: \(4, \{\}\)\}", text)
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}, may-alias\), "
                     r"\{1\}: \(1, \{\}, may-alias\) \}", text)


def test_kv_append_runs_per_shard_on_a_mesh(topo, chip_config):
    """`ServingServer(mesh=4)`: per shard, 4 of the 16 heads — one kernel,
    in place, no collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (DATA_AXIS, MODEL_AXIS))
    S, C, H, D = 48, 1024, 16, 64
    heads = NamedSharding(mesh, P(None, None, MODEL_AXIS, None))
    kv = jax.ShapeDtypeStruct((S, C, H, D), jnp.float32, sharding=heads)
    new = jax.ShapeDtypeStruct((S, 1, H, D), jnp.float32, sharding=heads)
    pos = jax.ShapeDtypeStruct((S,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        comp = compiled_append(kv, new, pos, interpret=False)
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert relayouts(text, S * C * H * D // 4) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 4 // 4
    assert not COLLECTIVE.search(text)


def compiled_step_layer(q, kv, new, pos, **how):
    """`flash_decode_append` on two donated caches, compiled."""
    return jax.jit(
        lambda q, k, v, kn, vn, pos: flash_decode_append(q, k, v, kn, vn,
                                                         pos, **how),
        donate_argnums=(1, 2)).lower(q, kv, kv, new, new, pos).compile()


def test_flash_decode_append_is_one_kernel_in_place(on_chip):
    """An attention layer of the `opt350m_batch_decode` cell's step: 48
    queries against two donated 48 x 1024 x 16 x 64 float32 buffers, the
    step's token appended by the decode kernel itself. ONE kernel, named
    `flash_decode`, on the buffers as they lie, its two slab outputs aliased
    onto the donated inputs (operands 4 and 5, after the lengths, the
    queries and the two new rows); no `kv_append`, no loop over the slots,
    nothing the size of a slab copied or allocated (HBM is 92 % full in the
    cell: one un-aliased 201 MB slab is a failed run)."""
    S, C, H, D = 48, 1024, 16, 64
    kv, new = on_chip((S, C, H, D), jnp.float32), on_chip((S, 1, H, D),
                                                          jnp.float32)
    comp = compiled_step_layer(new, kv, new, on_chip((S,), jnp.int32),
                               interpret=False)
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert "%flash_decode" in text and "%kv_append" not in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    assert "dynamic-update-slice" not in text
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 4
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(4, \{\}\), "
                     r"\{2\}: \(5, \{\}\)\}", text)


def test_flash_decode_append_grouped_heads_compiles_in_place(on_chip):
    """`granite4_h_micro`'s attention layer: 32 query heads on 8 K/V heads
    of 64, bfloat16, 64 slots of 1024 — the same kernel, in place."""
    S, C, Hq, H, D = 64, 1024, 32, 8, 64
    kv = on_chip((S, C, H, D), jnp.bfloat16)
    comp = compiled_step_layer(on_chip((S, 1, Hq, D), jnp.bfloat16), kv,
                               on_chip((S, 1, H, D), jnp.bfloat16),
                               on_chip((S,), jnp.int32), interpret=False)
    text = comp.as_text()
    assert text.count(KERNEL) == 1 and "%kv_append" not in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    assert comp.memory_analysis().alias_size_in_bytes == 2 * S * C * H * D * 2


@pytest.mark.parametrize("Hq", [16, 32], ids=["16_on_16", "32_on_16"])
def test_flash_decode_append_on_a_packed_cache_is_one_kernel_in_place(
        on_chip, Hq):
    """`opt350m_batch_decode`'s layer since PR 44: the 16 heads of 64 packed
    two to a row, the leaf declared [48, 1024, 8, 128] float32. THAT array
    the TPU stores row-major, one (8, 128) tile a position (the unpacked
    [48, 1024, 16, 64] lies positions-minor, `{1,3,2,0}`), so the row-major
    kernel's [S, C * 8, 128] view of it is a bitcast and the step's layer is
    ONE kernel named `flash_decode`, its two slab outputs aliased onto the
    donated caches (operands 6 and 7): no copy, transpose or update of a
    slab, no loop over the slots, no counted fallback, a 256-position block.
    The products compile at full float32 precision."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    S, C, H, D = 48, 1024, 16, 64
    rows = fa.packed_rows(H, D)
    assert rows == 8 and fa._rows_block(C, rows, 128, 4, 1024, False) == 256
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    kv = on_chip((S, C, rows, 128), jnp.float32)
    comp = compiled_step_layer(on_chip((S, 1, Hq, D), jnp.float32), kv,
                               on_chip((S, 1, H, D), jnp.float32),
                               on_chip((S,), jnp.int32), interpret=False)
    assert fallbacks.get() == before
    assert get_registry().get("flash_decode_block").get(
        C=C, H=H, D=D, itemsize=4) == 256
    text = comp.as_text()
    stored = re.findall(r"f32\[48,1024,8,128\](\{[^}]*\}) parameter\(", text)
    assert stored == ["{3,2,1,0:T(8,128)}"] * 2
    assert len(re.findall(r"f32\[48,8192,128\]\S* bitcast\(", text)) == 2
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert "%kv_append" not in text and "dynamic-update-slice" not in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 4
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(6, \{\}\), "
                     r"\{2\}: \(7, \{\}\)\}", text)


@pytest.mark.parametrize("S,C,H,D,ring", [
    (48, 1024, 4, 128, True), (48, 6144, 4, 128, False),
    (64, 1024, 8, 64, False), (64, 1024, 8, 64, True),
], ids=["ring_of_1024", "slab_of_6144", "packed_slab_of_1024",
        "packed_ring_of_1024"])
def test_flash_decode_append_on_half_tile_rows_is_one_kernel_in_place(
        on_chip, S, C, H, D, ring):
    """`mellum2_code_decode`'s two kinds of layer (PR 47): 32 query heads on
    4 K/V heads of 128, bfloat16, 48 slots — half an (8, 128) tile a
    position, so the leaf is declared in whole tiles, [48, C * 4 / 8, 8, 128]
    (`tiled_rows`): THAT array is the [S, C * 4, 128] buffer the row-major
    kernel copies from (a bitcast), and the token's 4 rows reach it with
    their tile (a copy to HBM may not start inside one: `Slice shape along
    dimension 1 must be aligned to tiling (8), but is 4` is what the plain
    leaf gave here). A window layer's ring of 1,024 positions is named
    `flash_decode_window`, a full layer's slab of 6,144 `flash_decode`: ONE
    kernel either way, its two slab outputs aliased onto the donated caches
    (operands 6 and 7), no copy, transpose or update of a slab, no loop over
    the slots, no counted fallback, a 256-position block.

    `granite4hmicro_chat_decode`'s layer (PR 49): 32 query heads on 8 K/V
    heads of 64, bfloat16, 64 slots of 1,024 — packed two to a row they are
    the same 4 rows a position, the leaf [64, 512, 8, 128], the view [64,
    4096, 128], and everything above holds of it (and of a ring of it)."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    Hq, R, W = 32, H * D // 128, 128
    tiles = fa.tiled_rows(C, H, D)
    assert tiles == C // 2 and R == 4 \
        and fa._rows_block(C, R, W, 2, 1024, False, tiled=True) == 256
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    kv = on_chip((S, tiles, 8, W), jnp.bfloat16)
    comp = compiled_step_layer(on_chip((S, 1, Hq, D), jnp.bfloat16), kv,
                               on_chip((S, 1, H, D), jnp.bfloat16),
                               on_chip((S,), jnp.int32), interpret=False,
                               ring=ring)
    assert fallbacks.get() == before
    assert get_registry().get("flash_decode_block").get(
        C=C, H=H, D=D, itemsize=2) == 256
    text = comp.as_text()
    stored = re.findall(
        rf"bf16\[{S},{tiles},8,128\](\{{[^}}]*\}}) parameter\(", text)
    assert stored == ["{3,2,1,0:T(8,128)(2,1)}"] * 2
    assert len(re.findall(rf"bf16\[{S},{C * R},128\]\S* bitcast\(",
                          text)) == 2
    assert text.count(KERNEL) == 1
    name = "flash_decode_window" if ring else "flash_decode"
    assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1
    assert ring or "%flash_decode_window" not in text
    assert "%kv_append" not in text and "dynamic-update-slice" not in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 2
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(6, \{\}\), "
                     r"\{2\}: \(7, \{\}\)\}", text)


@pytest.mark.parametrize("how,leaf", [
    (dict(n_heads=16), (48, 1024, 8, 128)),     # opt350m: packed
    (dict(n_heads=32, n_kv_heads=8, head_dim=128),
     (32, 1024, 8, 128)),                       # granite4_h_small, solar_open2
    (dict(n_heads=32, n_kv_heads=4, head_dim=128),
     (48, 512, 8, 128)),                        # mellum2's full layers (slab)
    (dict(n_heads=32, n_kv_heads=4, head_dim=128, window=256),
     (48, 128, 8, 128)),                        # ... and a window's ring
    (dict(n_heads=32, n_kv_heads=8), (64, 512, 8, 128)),  # granite4_h_micro
    (dict(n_heads=16, shards=4), (48, 1024, 16, 64)),     # opt350m, mesh of 4
    (dict(n_heads=32, n_kv_heads=8, shards=2), (64, 1024, 8, 64)),
    (dict(n_heads=32, n_kv_heads=8, use_pallas=False), (64, 1024, 8, 64)),
], ids=["opt350m", "granite4_h_small", "mellum2_slab", "mellum2_ring",
        "granite4_h_micro", "opt350m_mesh4", "granite4_h_micro_mesh2",
        "granite4_h_micro_no_kernel"])
def test_which_leaf_a_configurations_attention_layer_declares(how, leaf):
    """`SelfAttentionLayer.decode_entry` at the cells' heads, 1,024 positions:
    `granite4_h_micro`'s 8 K/V heads of 64 are declared packed in whole
    tiles since PR 49; every other configuration's leaf is what it was (and
    `granite4_h_micro`'s own under a model axis or without the kernel)."""
    from types import SimpleNamespace
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.recurrent import \
        SelfAttentionLayerModule
    how = {"head_dim": 64, "use_pallas": True, **how}
    geom = SimpleNamespace(slots=leaf[0], capacity=1024, dtype=jnp.bfloat16,
                           paged=False, model_shards=how.pop("shards", 1))
    entry = SelfAttentionLayerModule(SelfAttentionLayer(
        n_in=64, n_out=64, causal=True, **how)).decode_entry(geom)
    assert entry["k"].shape == entry["v"].shape == leaf


def test_windowed_flash_forward_compiles(on_chip):
    """A window layer's prefill of the longest prompt: 4,096 positions, 32
    heads of 128, bfloat16, a window of 1,024 in the kernel's default key
    blocks of 1,024 (swept on the chip: PERF.md section 6, PR 47)."""
    q = on_chip((1, 4096, 32, 128), jnp.bfloat16)
    text = compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=1024,
                                        interpret=False), q, q, q)
    assert text.count(KERNEL) == 1


def test_flash_decode_append_runs_per_shard_on_a_mesh(topo, chip_config):
    """`ServingServer(mesh=4)`: per shard, 4 of the 16 heads — the three
    outputs (the rows and the two slabs) head-sharded like the operands:
    one kernel, in place, no collective. 4 heads of 64 are 2 rows of 128
    lanes, no whole tile: on this mesh `opt350m`'s leaf stays unpacked
    (`packed_rows`; the engine's side of it: tests/test_decode_contract.py)
    and the program is the positions-minor kernel's, as before PR 44."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (DATA_AXIS, MODEL_AXIS))
    S, C, H, D = 48, 1024, 16, 64
    assert fa.packed_rows(H, D) == 8 and fa.packed_rows(H, D, 4) is None
    heads = NamedSharding(mesh, P(None, None, MODEL_AXIS, None))
    kv = jax.ShapeDtypeStruct((S, C, H, D), jnp.float32, sharding=heads)
    new = jax.ShapeDtypeStruct((S, 1, H, D), jnp.float32, sharding=heads)
    pos = jax.ShapeDtypeStruct((S,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        comp = compiled_step_layer(new, kv, new, pos, interpret=False)
    text = comp.as_text()
    assert text.count(KERNEL) == 1 and "%kv_append" not in text
    assert relayouts(text, S * C * H * D // 4) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 4 // 4
    assert not COLLECTIVE.search(text)


def test_loops_sees_the_per_slot_update(on_chip):
    """The guard has teeth: the same append as the vmapped
    `dynamic_update_slice` (`use_pallas=False`, and the fallback) compiles
    to one `while` loop over the slots for K and one for V — in place too,
    which is why only the loop tells the two apart."""
    S, C, H, D = 48, 1024, 16, 64
    kv, new = on_chip((S, C, H, D), jnp.float32), on_chip((S, 1, H, D),
                                                          jnp.float32)
    text = compiled_append(kv, new, on_chip((S,), jnp.int32),
                           use_pallas=False).as_text()
    assert KERNEL not in text
    scopes = loops(text)
    assert len(scopes) == 2 and all("scatter" in n for n in scopes)
    assert relayouts(text, S * C * H * D) == []


def test_unconditional_sorts_sees_a_sort_outside_a_conditional(on_chip):
    """The sampler's filter as it ran before PR 32, for every batch: the
    sort is in the entry computation. Behind `lax.cond` it is in the text
    and not on the unconditional path."""
    from deeplearning4j_tpu.decode.sampling import keep_mask
    args = (on_chip((8, 512), jnp.float32), on_chip((8,), jnp.int32),
            on_chip((8,), jnp.float32))
    text = compiled_text(keep_mask, *args)
    assert len(unconditional_sorts(text)) == 1
    text = compiled_text(
        lambda p, k, t: jax.lax.cond(
            jnp.any(k > 0), lambda: keep_mask(p, k, t),
            lambda: jnp.ones(p.shape, bool)), *args)
    assert sorts_only_under_a_conditional(text)


def test_relayouts_sees_a_transposed_cache(on_chip):
    """The guard has teeth: the same cache read through the training
    forward, which folds the heads into the batch in front of its kernel,
    shows the K and the V slab rewritten."""
    q = on_chip((48, 8, 16, 64), jnp.float32)
    kv = on_chip((48, 1024, 16, 64), jnp.float32)
    text = compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert len(relayouts(text, 48 * 1024 * 16 * 64)) >= 2


def test_flash_decode_paged_compiles(on_chip):
    slots, block, blocks_per_slot = 8, 16, 16        # capacity 256
    q = on_chip((slots, 1, 4, 64), jnp.bfloat16)
    pool = on_chip((slots * blocks_per_slot + 1, block, 4, 64), jnp.bfloat16)
    table = on_chip((slots, blocks_per_slot), jnp.int32)
    lengths = on_chip((slots,), jnp.int32)
    text = compiled_text(
        lambda q, k, v, t, n: flash_decode_paged(q, k, v, t, n,
                                                 interpret=False),
        q, pool, pool, table, lengths)
    assert text.count(KERNEL) == 1


def test_flash_decode_grouped_heads_compiles_on_the_cache_where_it_lies(
        on_chip):
    """granite4_h_micro's attention layers: 32 query heads over a bfloat16
    cache of 8 K/V heads; the key block follows the QUERY heads (512, where
    the 8 K/V heads alone would take the whole capacity), and the cache is
    still read through a bitcast."""
    S, C, Hq, H, D = 64, 1024, 32, 8, 64
    assert fa._decode_block(C, Hq, D, 2, 1024, False) == 512
    text = compiled_text(
        lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
        on_chip((S, 1, Hq, D), jnp.bfloat16),
        on_chip((S, C, H, D), jnp.bfloat16),
        on_chip((S, C, H, D), jnp.bfloat16), on_chip((S,), jnp.int32))
    assert text.count(KERNEL) == 1
    assert relayouts(text, S * C * H * D) == []


def test_ssm_step_compiles_in_place(on_chip):
    """The Mamba-2 state update at the cell's size, 64 slots x [128, 4096]
    float32: one kernel, the donated state aliased onto its output (no
    second 134 MB buffer, no copy of it)."""
    from deeplearning4j_tpu.kernels import ssm_step
    S, N, C = 64, 128, 4096
    comp = jax.jit(
        lambda st, a, x, b, c: ssm_step(st, a, x, b, c, interpret=False),
        donate_argnums=(0,)).lower(
            on_chip((S, N, C), jnp.float32), on_chip((S, C), jnp.float32),
            on_chip((S, C), jnp.float32), on_chip((S, N), jnp.float32),
            on_chip((S, N), jnp.float32)).compile()
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%ssm_step[.\d]* = ", text)) == 1
    assert relayouts(text, S * N * C) == []
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == S * N * C * 4
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("S,C,Hq", [(32, 1024, 32), (192, 4096, 64)],
                         ids=["granite4_h_small", "solar_open2"])
def test_flash_decode_append_at_head_dim_128_reads_the_cache_where_it_lies(
        on_chip, S, C, Hq):
    """An attention layer of a step over a bfloat16 cache of 8 K/V heads of
    128 — granite4_h_small's (32 query heads, 32 slots of 1024) and
    solar_open2's (64 query heads, 192 slots of 4096: two slabs of 1.6 GB).
    At head_dim 128 the cache is row-major; the step's kernel reads it so
    (its [S, C * 8, 128] view is a bitcast of the buffer) and writes the
    token's rows itself: ONE kernel named `flash_decode`, both slabs aliased
    onto the donated caches, no copy, transpose or scatter of a slab, no
    loop over the slots, no counted fallback. Until PR 43 this shape paid a
    transposing copy of K and of V every step (`flash_decode` alone, the
    read-only entry the paged path and the fallback use, still does: two
    relayouts, priced in PERF.md)."""
    from deeplearning4j_tpu.telemetry.registry import get_registry
    H, D = 8, 128
    assert fa._rows_block(C, H, D, 2, 1024, False) == 256
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    kv = on_chip((S, C, H, D), jnp.bfloat16)
    comp = compiled_step_layer(on_chip((S, 1, Hq, D), jnp.bfloat16), kv,
                               on_chip((S, 1, H, D), jnp.bfloat16),
                               on_chip((S,), jnp.int32), interpret=False)
    assert fallbacks.get() == before
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert "%kv_append" not in text and "dynamic-update-slice" not in text
    assert relayouts(text, S * C * H * D) == []
    assert loops(text) == []
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * S * C * H * D * 2
    assert mem.temp_size_in_bytes < 16 << 20
    if S == 32:
        text = compiled_text(
            lambda q, k, v, n: flash_decode(q, k, v, n, scale=0.0078125,
                                            interpret=False),
            on_chip((S, 1, Hq, D), jnp.bfloat16), kv, kv,
            on_chip((S,), jnp.int32))
        assert text.count(KERNEL) == 1
        assert len(relayouts(text, S * C * H * D)) == 2


@pytest.mark.parametrize("H,shape", [(8, (1, 4)), (16, (2, 2))],
                         ids=["2_heads_a_shard", "8_heads_a_shard"])
def test_flash_decode_append_at_head_dim_128_chooses_by_the_shards_heads(
        topo, chip_config, H, shape):
    """`ServingServer(mesh=4)` over a bfloat16 cache of head_dim 128. The
    row-major kernel's [S, C * H, D] view is the buffer only where the K/V
    heads a SHARD holds fill a tile's 8 sublanes, so the choice is made on
    that count: granite4_h_small's and solar_open2's 8 K/V heads split four
    ways (2 a shard) give way, counted, to `kv_append` (at this head_dim
    XLA's update, a loop over the slots) then `flash_decode` as they did
    before PR 43, and compile; 16 K/V heads split two ways (8 a shard, the
    slots over the data axis) take the one kernel per shard, in place. No
    collective either way."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding import DATA_AXIS, MODEL_AXIS
    from deeplearning4j_tpu.telemetry.registry import get_registry
    mesh = Mesh(np.array(topo.devices).reshape(shape), (DATA_AXIS, MODEL_AXIS))
    S, C, D = 32, 1024, 128
    heads = NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS, None))
    q = jax.ShapeDtypeStruct((S, 1, 4 * H, D), jnp.bfloat16, sharding=heads)
    kv = jax.ShapeDtypeStruct((S, C, H, D), jnp.bfloat16, sharding=heads)
    new = jax.ShapeDtypeStruct((S, 1, H, D), jnp.bfloat16, sharding=heads)
    pos = jax.ShapeDtypeStruct((S,), jnp.int32,
                               sharding=NamedSharding(mesh, P(DATA_AXIS)))
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    gave_way = dict(kernel="flash_decode", path="kv_append+flash_decode",
                    shape=f"C={C},D={D},interpret=False")
    before = fallbacks.get(**gave_way)
    with jax.set_mesh(mesh):
        assert fa._heads_per_shard(H) == H // shape[1]
        comp = compiled_step_layer(q, kv, new, pos, interpret=False)
    assert fallbacks.get(**gave_way) - before == (H // shape[1] != 8)
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert not COLLECTIVE.search(text)
    if H // shape[1] != 8:
        assert len(loops(text)) == 2
    else:
        per_shard = S * C * H * D // 4
        assert relayouts(text, per_shard) == []
        assert loops(text) == []
        assert "dynamic-update-slice" not in text
        assert comp.memory_analysis().alias_size_in_bytes == 2 * per_shard * 2


@pytest.mark.parametrize("tokens,tm", [(32, 16), (256, 128), (2048, 512)],
                         ids=["step", "prefill_256", "rows_2048"])
def test_expert_gmm_compiles_at_the_cells_widths(on_chip, tokens, tm):
    """One layer's grouped product of granite4_h_small: 18 held experts of
    4096 -> 2 x 768 -> 4096 in bfloat16, rows for `tokens` x 10 pairs in
    tiles of `tm`: ONE kernel named after its caller, the matrices (340 MB)
    arguments it reads where they lie, no copy of them."""
    from deeplearning4j_tpu.kernels.expert_gmm import expert_gmm, row_tile
    assert row_tile(tokens * 10, 72, 2) == tm
    held, d, hidden = 18, 4096, 768
    tiles = tokens * 10 // tm + held
    comp = jax.jit(lambda r, a, b, g, n: expert_gmm(
        r, a, b, g, n[0], interpret=False, tag=f"{tokens}x1")).lower(
            on_chip((tiles * tm, d), jnp.bfloat16),
            on_chip((held, d, 2 * hidden), jnp.bfloat16),
            on_chip((held, hidden, d), jnp.bfloat16),
            on_chip((tiles,), jnp.int32), on_chip((1,), jnp.int32)).compile()
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(rf"%expert_gmm_{tokens}x1[.\d]* = ", text)) == 1
    assert relayouts(text, held * d * hidden) == []
    assert comp.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("S,H", [(128, 32), (192, 64)],
                         ids=["ling3_flash", "solar_open2"])
def test_kda_step_compiles_in_place(on_chip, S, H):
    """The delta-rule state update at `ling3_flash`'s size, 128 slots x [32,
    128, 128] float32 (one head group a slot), and at `solar_open2`'s, 192
    slots x [64, 128, 128] (4 MB a slot: two groups of 32, 805 MB of state):
    one kernel — a group's per-head columns sliced from ONE packed lane
    tile, which Mosaic has to accept —, the donated state aliased onto its
    output (no second buffer, no copy of it), no counted fallback."""
    from deeplearning4j_tpu.kernels import kda_step
    from deeplearning4j_tpu.telemetry.registry import get_registry
    D = 128
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    row = on_chip((S, H, D), jnp.float32)
    comp = jax.jit(
        lambda st, a, k, q, b, v: kda_step(st, a, k, q, b, v,
                                           interpret=False),
        donate_argnums=(0,)).lower(
            on_chip((S, H, D, D), jnp.float32), row, row, row,
            on_chip((S, H), jnp.float32), row).compile()
    assert fallbacks.get() == before
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%kda_step[.\d]* = ", text)) == 1
    assert relayouts(text, S * H * D * D) == []
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * D * D * 4
    columns = S * D * 4 * H * 4     # the packed [S, 128, 4 H] float32 operand
    assert mem.temp_size_in_bytes < max(16 << 20, columns + (1 << 20))


def test_mla_decode_and_latent_append_compile_on_the_padded_row(on_chip):
    """The latent cache at `ling3_flash`'s size, 128 slots x 3,072 x 640
    bfloat16 (576 padded to five lane tiles: a 576-wide copy is refused, the
    slice must be aligned to the tiling): the decode kernel reads the slab
    where it lies, the append writes it in place."""
    from deeplearning4j_tpu.kernels import latent_append, mla_decode
    S, C, W, H, R = 128, 3072, 640, 32, 512
    slab = on_chip((S, C, W), jnp.bfloat16)
    attend = lambda q, lat, n: mla_decode(q, lat, n, rank=R, interpret=False)
    args = on_chip((S, H, W), jnp.bfloat16), slab, on_chip((S,), jnp.int32)
    text = compiled_text(attend, *args)
    assert text.count(KERNEL) == 1
    assert len(re.findall(r"%mla_decode[.\d]* = ", text)) == 1
    assert relayouts(text, S * C * W) == []
    assert mla_row_tiles(attend, *args) == mla._DEPTH + 1 > 2
    comp = jax.jit(
        lambda lat, rows, pos: latent_append(lat, rows, pos, interpret=False),
        donate_argnums=(0,)).lower(
            slab, on_chip((S, W), jnp.bfloat16),
            on_chip((S,), jnp.int32)).compile()
    text = comp.as_text()
    assert len(re.findall(r"%latent_append[.\d]* = ", text)) == 1
    assert relayouts(text, S * C * W) == [] and loops(text) == []
    assert comp.memory_analysis().alias_size_in_bytes == S * C * W * 2
    with pytest.raises(Exception, match="aligned to tiling"):
        compiled_text(
            lambda q, lat, n: mla_decode(q, lat, n, rank=R, interpret=False),
            on_chip((S, H, 576), jnp.bfloat16),
            on_chip((S, C, 576), jnp.bfloat16), on_chip((S,), jnp.int32))


def test_mla_kernels_compile_at_64_heads_and_a_4096_bucket(on_chip):
    """`kimi_k27_code`'s shapes: `mla_decode` at 64 query rows a slot on a
    128 x 6,144 x 640 bfloat16 slab it reads where it lies, and the
    blockwise prefill — 128 | 64-wide keys against 128-wide values, the
    rotary key one `[1, T, 64]` operand for all 64 heads — over a 4,096
    bucket with the prefill's mask: one kernel named after its bucket, and
    no `[64, T, T]` score matrix among its temporaries (4.3 GB; the head
    folds are 0.2 GB)."""
    from deeplearning4j_tpu.kernels import mla_decode, mla_prefill
    S, C, W, H, R, T = 128, 6144, 640, 64, 512, 4096
    attend = lambda q, lat, n: mla_decode(q, lat, n, rank=R, interpret=False)
    args = (on_chip((S, H, W), jnp.bfloat16),
            on_chip((S, C, W), jnp.bfloat16), on_chip((S,), jnp.int32))
    text = compiled_text(attend, *args)
    assert text.count(KERNEL) == 1
    assert relayouts(text, S * C * W) == []
    assert mla_row_tiles(attend, *args) == mla._DEPTH + 1 > 2
    head = lambda d: on_chip((1, T, H, d), jnp.bfloat16)
    comp = jax.jit(lambda qn, qp, kn, kp, v, m: mla_prefill(
        qn, qp, kn, kp, v, scale=0.1447, key_mask=m, interpret=False)).lower(
            head(128), head(64), head(128), on_chip((1, T, 64), jnp.bfloat16),
            head(128), on_chip((1, T), jnp.float32)).compile()
    text = comp.as_text()
    assert text.count(KERNEL) == 1
    assert len(re.findall(rf"%mla_prefill_{T}[.\d]* = ", text)) == 1
    assert comp.memory_analysis().temp_size_in_bytes < 1 << 28


def test_kimi_decode_step_and_prefill_compile_with_their_kernels(
        one_chip, chip_config, monkeypatch):
    """`kimi_k2_lm` (the dense layer and two expert layers) at two of the
    configuration's 64 heads, its own query latent of 1,536, key/value
    latent of 512, 128 | 64 | 128 heads and 2,048-wide experts (12 of 384
    held), bfloat16, 16 slots of 256: every layer of the step is one
    `latent_append` and one `mla_decode`, an expert layer one
    `expert_gmm_16x1`; no loop, no copy of a latent slab. Its 128-token
    prefill, made to attend blockwise as the cell's buckets do (64 heads at
    2,048 positions and more), is one `mla_prefill_128` a layer; no kernel
    gave way. (The whole step at the cell's size — 128 slots of 6,144, 64
    heads, five layers — compiles here in 12 s with 12.03 GB of arguments and
    0.046 GB of temporaries, 14 kernels, its 4,096-token prefill in 22 s
    with 1.18 GB: PERF.md section 4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.zoo.models import kimi_k2_lm
    for module in ("mla_decode", "mla_prefill", "expert_gmm"):
        monkeypatch.setattr(
            importlib.import_module("deeplearning4j_tpu.kernels." + module),
            "_interpret_default", lambda: False)
    yarn = {"factor": 64, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
    net = kimi_k2_lm(vocab_size=512, d_model=256, n_layers=3, n_heads=2,
                     yarn=yarn, experts_held=12, dtype="bfloat16",
                     use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=256)
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert text.count(KERNEL) == 8
    assert len(re.findall(r"%mla_decode[.\d]* = ", text)) == 3
    assert len(re.findall(r"%latent_append[.\d]* = ", text)) == 3
    assert len(re.findall(r"%expert_gmm_16x1[.\d]* = ", text)) == 2
    assert loops(text) == [] and text.count(" conditional(") == 1
    assert relayouts(text, 16 * 256 * 640) == []
    monkeypatch.setattr(
        importlib.import_module("deeplearning4j_tpu.nn.layers.mla"),
        "PLAIN_SCORE_BYTES", 0)
    text = _prefill_text(eng, 128, one_chip)
    assert len(re.findall(r"%mla_prefill_128[.\d]* = ", text)) == 3
    assert len(re.findall(r"%expert_gmm_1x128[.\d]* = ", text)) == 2
    assert "%mla_decode" not in text and loops(text) == []
    assert fallbacks.get() == before


def test_kda_chunked_forms_no_chunk_square_of_exponents(on_chip):
    """The prefill's delta rule at `solar_open2`'s size, [1, 1024, 64, 128]
    float32 in chunks of 64: the scores are formed by sub-blocks of 16, so
    no instruction of the program — in a fusion or out of one — has the [64,
    64, 64, 128] shape of a whole chunk's exponents, and a scan body (the
    cost analysis counts the loop's body once) exponentiates under 40 % of
    the 34.6 M elements it did when it formed that square (10.8 M: the four
    diagonal blocks 8.4 M, the boundaries' factors and the chunk's own)."""
    from deeplearning4j_tpu.nn.layers.kda import kda_chunked
    T, H, D, Q = 1024, 64, 128, 64
    x = on_chip((1, T, H, D), jnp.float32)
    comp = jax.jit(lambda q, k, v, g, beta: kda_chunked(
        q, k, v, g, beta, Q)).lower(x, x, x, x,
                                    on_chip((1, T, H), jnp.float32)).compile()
    text = comp.as_text()
    assert f"[{Q // 16},16,16,{H},{D}]" in text      # the diagonal blocks
    assert not re.search(rf"\[(?:\d+,)*{Q},{Q},{H},{D}\]", text)
    assert comp.cost_analysis()["transcendentals"] < 0.4 * 34.6e6


def test_untileable_shape_has_no_compiled_plan():
    """Why chip_smoke.py asks for prompts of 128 tokens and more: compiled,
    the key block must be a multiple of 128, so a 64-token prefill bucket or
    a 64-entry cache has no plan and gives way to the pure-JAX path."""
    assert fa._plan(64, 64, 64, 256, 1024, interpret=False) is None
    assert fa._plan(8, 64, 64, 8, 1024, interpret=False) is None
    assert fa._plan(128, 128, 64, 256, 1024, interpret=False) == (128, 128)
    assert fa._plan(64, 64, 64, 256, 1024, interpret=True) == (64, 64)


# ------------------------------------------------ whole steps of the models
def _abstract(tree, sharding):
    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(
                a.shape, jax.dtypes.canonicalize_dtype(a.dtype),
                sharding=sharding)
        return a
    return jax.tree_util.tree_map(leaf, tree)


@pytest.fixture(scope="module")
def lm_engine(chip_config):
    """chip_smoke.py's decoder: the 256-wide transformer_lm behind an
    8-slot, 256-token decode engine (built on the CPU; only its shapes go to
    the compiler)."""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=256, d_model=256, n_layers=4, n_heads=4,
                         use_pallas=True, compute_dtype="bfloat16").init()
    return DecodeEngine(net, slots=8, max_len=256)


@pytest.fixture(scope="module")
def packed_lm_engine(chip_config):
    """The same decoder with `opt350m`'s heads, 16 of 64 in float32: the one
    shape of the two whose K/V leaves pack (`packed_rows`)."""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import transformer_lm
    net = transformer_lm(vocab_size=256, d_model=1024, n_layers=2, n_heads=16,
                         ffn_mult=1, use_pallas=True).init()
    return DecodeEngine(net, slots=8, max_len=256)


def _prefill_text(eng, bucket, sharding):
    """The optimized HLO of `eng`'s prefill of `bucket` tokens into slot 0,
    compiled for `sharding`'s device. Its outputs are the cache, the first
    id, the last position's probabilities and, the one output the loop that
    keeps a step in flight added, the [slots] vector of next ids."""
    lowered = eng._build_prefill(bucket).lower(*_abstract(
        (eng.model.params, eng.model.states, jax.eval_shape(eng._cache_zeros),
         np.int32(0), np.zeros((bucket,), np.int32), np.int32(bucket - 1),
         eng._greedy_slot_ops), sharding), None,
        *_abstract((np.zeros((eng.slots,), np.int32),), sharding))
    cache, nid, probs, next_ids = lowered.out_info
    assert (nid.shape, probs.shape) == ((), (eng.vocab,))
    assert (next_ids.shape, next_ids.dtype) == ((eng.slots,), jnp.int32)
    return lowered.compile().as_text()


@pytest.mark.parametrize("which,leaf", [
    ("lm_engine", (8, 64, 8, 128)), ("packed_lm_engine", (8, 256, 8, 128))],
    ids=["4_heads_of_64", "16_heads_of_64_packed"])
def test_decode_step_compiles_with_kernel(request, which, leaf, one_chip,
                                          chip_config, monkeypatch):
    """At a shape whose K/V leaves pack to two rows a position and are
    declared in whole tiles since PR 49 (4 heads of 64: `[8, 256 * 2 / 8, 8,
    128]`, four positions a tile) and at one that packs to whole tiles (16
    heads of 64: `[8, 256, 8, 128]`): the row-major kernel on both."""
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    eng = request.getfixturevalue(which)
    layers = len(eng._entries)
    assert all(e["k"].shape == leaf for e in eng._entries.values())
    args = _abstract((eng.model.params, eng.model.states,
                      jax.eval_shape(eng._cache_zeros),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    # per layer ONE kernel: the decode kernel appends the step's token
    assert text.count(KERNEL) == layers
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == layers
    assert "%kv_append" not in text
    # and nothing in the step rewrites a K or V slab: the kernel reads the
    # donated cache where it lies and writes the token's tile in place
    assert relayouts(text, int(np.prod(leaf))) == []
    # no loop over the slots is left (the append as XLA's per-slot update)
    assert loops(text) == []
    assert "dynamic-update-slice" not in text
    assert sorts_only_under_a_conditional(text)


@pytest.mark.parametrize("bucket", [128, 256])
def test_prefill_bucket_compiles_with_kernel(lm_engine, one_chip,
                                             chip_config, monkeypatch,
                                             bucket):
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    eng = lm_engine
    text = _prefill_text(eng, bucket, one_chip)
    assert text.count(KERNEL) == 4          # one masked flash per layer
    assert sorts_only_under_a_conditional(text)


def test_programs_of_the_loop_with_a_step_in_flight_keep_their_kernels(
        one_chip, chip_config, monkeypatch):
    """The two programs the decode loop queues behind one another, at
    `opt350m_batch_decode`'s depth and cache geometry (24 layers, heads of
    64, float32, 48 slots of 1024; a quarter of its width and a small
    vocabulary, which no count below depends on). The step takes its ids as
    the [slots] vector the step before it returned — abstractly the host
    vector it took before, so one program: 24 `flash_decode` kernels that
    append as they read, one conditional, no loop, no copy of a slab. The
    256-token prefill takes that vector too and returns it with its slot's
    entry set (`_prefill_text` holds the outputs to that): 24 masked flash
    kernels, one conditional, no loop."""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import transformer_lm
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    net = transformer_lm(vocab_size=512, d_model=256, n_layers=24, n_heads=4,
                         use_pallas=True).init()
    eng = DecodeEngine(net, slots=48, max_len=1024)
    next_ids = np.zeros((eng.slots,), np.int32)     # a step's own output
    lowered = eng._build_step().lower(*_abstract(
        (net.params, net.states, jax.eval_shape(eng._cache_zeros), next_ids,
         eng._greedy_step_ops), one_chip), None)
    assert lowered.out_info[1].shape == next_ids.shape \
        and lowered.out_info[1].dtype == next_ids.dtype
    text = lowered.compile().as_text()
    assert text.count(KERNEL) == 24
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 24
    assert "%kv_append" not in text and "dynamic-update-slice" not in text
    assert text.count(" conditional(") == 1
    assert sorts_only_under_a_conditional(text)
    assert loops(text) == []
    assert relayouts(text, eng.slots * eng.capacity * 256) == []
    text = _prefill_text(eng, 256, one_chip)
    assert text.count(KERNEL) == 24
    assert text.count(" conditional(") == 1
    assert sorts_only_under_a_conditional(text)
    assert loops(text) == []
    assert relayouts(text, eng.slots * eng.capacity * 256) == []


def test_hybrid_decode_step_compiles_with_both_kinds_of_kernel(
        one_chip, chip_config, monkeypatch):
    """granite4_h_micro's two kinds of block (Mamba-2, attention, Mamba-2) at
    a quarter of its widths, bfloat16, 16 slots of 256: per Mamba-2 layer
    one `ssm_step`, for the attention layer (grouped heads) one
    `flash_decode` that appends; no loop over the slots, no copy of a state
    or of a K/V slab. (The whole 40-layer step at the cell's size compiles here in 31 s
    with 12.2 GB of arguments and 0.116 GB of temporaries: PERF.md §4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import granite_hybrid_lm
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(
        importlib.import_module("deeplearning4j_tpu.kernels.ssm_step"),
        "_interpret_default", lambda: False)
    net = granite_hybrid_lm(
        vocab_size=512, d_model=512, n_layers=3, n_heads=8, n_kv_heads=2,
        attention_layers=(1,), mamba_d_head=64, mamba_d_state=128,
        embedding_multiplier=12, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=8, dtype="bfloat16",
        use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=256)
    # 2 K/V heads of 64: one row of 128 lanes a position, eight to a tile
    assert eng._entries["b1_attn"]["k"].shape == (16, 32, 8, 128)
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert text.count(KERNEL) == 3
    assert len(re.findall(r"%ssm_step[.\d]* = ", text)) == 2
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert "%kv_append" not in text
    assert relayouts(text, 16 * 256 * 2 * 64) == []
    assert loops(text) == []
    assert sorts_only_under_a_conditional(text)
    text = _prefill_text(eng, 128, one_chip)
    assert text.count(KERNEL) == 1          # the attention layer's flash
    assert sorts_only_under_a_conditional(text)


def test_routed_decode_step_compiles_with_one_expert_kernel_a_layer(
        one_chip, chip_config, monkeypatch):
    """Two blocks (Mamba-2, attention) with routed experts beside the shared
    one, at an eighth of granite4_h_small's widths, bfloat16, 16 slots of
    256: a block's expert layer is ONE `expert_gmm_16x1` kernel in the step
    and one `expert_gmm_1x128` in a prefill, and routing adds no loop (its
    tile table is a compare and a sum, not a `searchsorted`). (The whole
    10-layer step at the cell's size compiles here in 36 s with 7.47 GB of
    arguments and 0.17 GB of temporaries: PERF.md section 4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import granite_hybrid_lm
    for module in ("flash_attention", "ssm_step", "expert_gmm"):
        monkeypatch.setattr(
            importlib.import_module("deeplearning4j_tpu.kernels." + module),
            "_interpret_default", lambda: False)
    net = granite_hybrid_lm(
        vocab_size=512, d_model=512, n_layers=2, n_heads=8, n_kv_heads=2,
        attention_layers=(1,), mamba_d_head=64, mamba_d_state=128,
        embedding_multiplier=12, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=16, ffn_mult=0.375,
        n_experts=16, experts_per_token=4, expert_hidden=128, experts_held=4,
        first_expert=4, dtype="bfloat16", use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=256)
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert text.count(KERNEL) == 4      # + the attention layer's one
    assert len(re.findall(r"%expert_gmm_16x1[.\d]* = ", text)) == 2
    assert len(re.findall(r"%ssm_step[.\d]* = ", text)) == 1
    assert loops(text) == []
    conditionals = text.count(" conditional(")          # the sampler's one
    assert conditionals == 1
    text = _prefill_text(eng, 128, one_chip)
    assert len(re.findall(r"%expert_gmm_1x128[.\d]* = ", text)) == 2
    assert loops(text) == []
    # 128 x 4 pairs in tiles of 64 + 4 experts' spare tiles: 768 rows of
    # 512 — gathered, not zeroed afterwards, not copied
    rows = 128 * 4 + 4 * 64
    assert under_scope(text, "moe_dispatch", "select_n", rows * 512) == []
    assert len(under_scope(text, "moe_dispatch", "gather", rows * 512)) >= 2
    assert operand_copies(text, rows * 512) == []
    assert text.count(" conditional(") == conditionals


def test_ling_decode_step_compiles_with_its_three_new_kernels(
        one_chip, chip_config, monkeypatch):
    """One period of `ling_hybrid_lm` (5 KDA + 1 MLA; two dense and four
    routed ffns) at two of the configuration's 32 heads, its own 128-wide
    heads, 512-wide latent and 768-wide experts, bfloat16, 16 slots of 256:
    a KDA layer is ONE `kda_step` kernel in the step, the MLA layer one
    `latent_append` and one `mla_decode`, an expert layer one
    `expert_gmm_16x1`; no loop, no copy of a state or of the latent slab.
    (The whole step at the cell's size — 128 slots of 3,072, 32 heads —
    compiles here in 19 s with 6.05 GB of arguments and 0.02 GB of
    temporaries, its 1,024-token prefill in 45 s with 0.26 GB: PERF.md
    section 4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.zoo.models import ling_hybrid_lm
    for module in ("kda_step", "mla_decode", "expert_gmm"):
        monkeypatch.setattr(
            importlib.import_module("deeplearning4j_tpu.kernels." + module),
            "_interpret_default", lambda: False)
    net = ling_hybrid_lm(vocab_size=512, d_model=256, n_layers=6, n_heads=2,
                         experts_held=64, dtype="bfloat16",
                         use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=256)
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert text.count(KERNEL) == 11
    assert len(re.findall(r"%kda_step[.\d]* = ", text)) == 5
    assert len(re.findall(r"%mla_decode[.\d]* = ", text)) == 1
    assert len(re.findall(r"%latent_append[.\d]* = ", text)) == 1
    assert len(re.findall(r"%expert_gmm_16x1[.\d]* = ", text)) == 4
    assert loops(text) == [] and text.count(" conditional(") == 1
    assert relayouts(text, 16 * 2 * 128 * 128) == []
    assert relayouts(text, 16 * 256 * 640) == []
    text = _prefill_text(eng, 128, one_chip)
    assert len(re.findall(r"%expert_gmm_1x128[.\d]* = ", text)) == 4
    assert "kda_step" not in text and "mla_decode" not in text


def test_solar_decode_step_compiles_with_its_kernels_and_no_copy_of_a_slab(
        one_chip, chip_config, monkeypatch):
    """One period of `solar_hybrid_lm` (gated attention, then KDA x 3; four
    routed ffns) at 16 of the configuration's 64 heads on its own 8 K/V
    heads of 128, its 128 x 128 delta-rule heads and rank-128 gates,
    bfloat16, 16 slots of 256: the attention layer is ONE `flash_decode`
    (the row-major kernel: the token's rows written by it), a KDA layer one
    `kda_step`, an expert layer one `expert_gmm_16x1`; no loop over the
    slots, no copy, transpose or scatter of a K/V slab or of a state, and
    no kernel gave way (the counted fallbacks stand still). (The whole step
    at the cell's size — 192 slots of 4,096, 64 heads — compiles here in
    37 s with 12.34 GB of arguments and 0.107 GB of temporaries, 8 kernels
    and no relayout of a slab or a state, its 1,024-token prefill in 49 s:
    PERF.md section 4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.zoo.models import solar_hybrid_lm
    for module in ("flash_attention", "kda_step", "expert_gmm"):
        monkeypatch.setattr(
            importlib.import_module("deeplearning4j_tpu.kernels." + module),
            "_interpret_default", lambda: False)
    net = solar_hybrid_lm(vocab_size=512, d_model=256, n_layers=4, n_heads=16,
                          n_kv_heads=8, n_experts=32, experts_held=8,
                          expert_hidden=128, shared_hidden=128,
                          dtype="bfloat16", use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=256)
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert fallbacks.get() == before
    assert text.count(KERNEL) == 8
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert len(re.findall(r"%kda_step[.\d]* = ", text)) == 3
    assert len(re.findall(r"%expert_gmm_16x1[.\d]* = ", text)) == 4
    assert "%kv_append" not in text
    assert loops(text) == [] and text.count(" conditional(") == 1
    assert relayouts(text, 16 * 256 * 8 * 128) == []     # a K or V slab
    assert relayouts(text, 16 * 16 * 128 * 128) == []    # a layer's state
    text = _prefill_text(eng, 128, one_chip)
    assert len(re.findall(r"%expert_gmm_1x128[.\d]* = ", text)) == 4
    assert "kda_step" not in text and "%flash_decode" not in text


def test_mellum_decode_step_compiles_with_one_kernel_a_layer_and_no_copy(
        one_chip, chip_config, monkeypatch):
    """One period of `mellum_lm` (three sliding-window layers and a full one
    under YaRN, 32 query heads on the configuration's own 4 K/V heads of
    128; four routed ffns) at d_model 256, bfloat16, 16 slots of 2,048: a
    window layer is ONE `flash_decode_window` on its ring of 1,024 positions,
    the full layer ONE `flash_decode` on its slab, both leaves in whole
    tiles, an expert layer one `expert_gmm_16x1`; no instruction of the step
    copies a slab or loops over the slots, and no kernel gave way (the
    counted fallbacks stand still, in the prefill too). (The whole 28-layer
    step at the cell's size — 48 slots of 6,144 — compiles here in 37 s with
    13.32 GB of arguments and 0.056 GB of temporaries, 56 kernels, its
    4,096-token prefill in 53 s with 0.58 GB: PERF.md section 4.)"""
    from deeplearning4j_tpu.decode.engine import DecodeEngine
    from deeplearning4j_tpu.telemetry.registry import get_registry
    from deeplearning4j_tpu.zoo.models import mellum_lm
    for module in ("flash_attention", "expert_gmm"):
        monkeypatch.setattr(
            importlib.import_module("deeplearning4j_tpu.kernels." + module),
            "_interpret_default", lambda: False)
    yarn = {"factor": 16, "original_max_position_embeddings": 8192,
            "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782}
    net = mellum_lm(vocab_size=512, d_model=256, n_layers=4, n_heads=32,
                    n_kv_heads=4, yarn=yarn, n_experts=32, experts_held=8,
                    expert_hidden=128, dtype="bfloat16",
                    use_pallas=True).init()
    eng = DecodeEngine(net, slots=16, max_len=2048)
    assert [e["k"].shape for e in eng._entries.values()] \
        == [(16, 512, 8, 128)] * 3 + [(16, 1024, 8, 128)]
    fallbacks = get_registry().counter("pallas_fallback_total", "")
    before = fallbacks.get()
    args = _abstract((net.params, net.states, eng.init_cache(),
                      np.zeros((eng.slots,), np.int32),
                      eng._greedy_step_ops), one_chip)
    text = eng._build_step().lower(*args, None).compile().as_text()
    assert text.count(KERNEL) == 8
    assert len(re.findall(r"%flash_decode_window[.\d]* = ", text)) == 3
    assert len(re.findall(r"%flash_decode[.\d]* = ", text)) == 1
    assert len(re.findall(r"%expert_gmm_16x1[.\d]* = ", text)) == 4
    assert "%kv_append" not in text
    assert loops(text) == []
    assert relayouts(text, 16 * 1024 * 4 * 128) == []    # a ring
    conditionals = text.count(" conditional(")          # the sampler's one
    assert conditionals == 1
    text = _prefill_text(eng, 2048, one_chip)
    assert len(re.findall(r"%flash_fwd[.\d]* = ", text)) == 4
    assert len(re.findall(r"%expert_gmm_1x2048[.\d]* = ", text)) == 4
    assert "%flash_decode" not in text and loops(text) == []
    assert fallbacks.get() == before
    # an expert layer's operand, 2,048 x 8 pairs in tiles of 512 + 8 spare
    # tiles = 20,480 rows of 256: one gather, nothing zeroed after it, no
    # copy of it, and the program branches no more than the step does
    rows = 2048 * 8 + 8 * 512
    assert under_scope(text, "moe_dispatch", "select_n", rows * 256) == []
    assert operand_copies(text, rows * 256) == []
    assert text.count(" conditional(") == conditionals


@pytest.mark.slow
def test_resnet50_train_step_fits_one_chip(one_chip, chip_config):
    """chip_smoke.py's train phase: ResNet-50, batch 256, 224 x 224, bf16,
    uint8 pixels + int32 ids with the ingest fused into the step."""
    from deeplearning4j_tpu.etl.device_transform import DeviceIngest
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.models import resnet50
    net = resnet50(num_classes=1000, image_size=224,
                   updater=Nesterovs(learning_rate=0.05, momentum=0.9),
                   compute_dtype="bfloat16").init()
    net.set_ingest(DeviceIngest(one_hot_labels=1000))
    args = _abstract((net.params, net.opt_state, net.states, net._rng,
                      [np.zeros((256, 224, 224, 3), np.uint8)],
                      [np.zeros((256,), np.int32)]), one_chip)
    comp = net._make_train_step().lower(*args, None, None, None).compile()
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
