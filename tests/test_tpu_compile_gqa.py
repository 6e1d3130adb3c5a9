"""The grouped-heads decode kernel, the experts' grouped-matmul kernel, the
gated delta rule's decode kernel (with and without its delta term), the
block-list decode read and the block selection's scoring kernels (a decode
step's and a prefill stretch's) compiled by Mosaic for a DESCRIBED v5e at the cells' real widths, here,
without a chip: what interpret mode cannot refuse (a slice off the tiling,
too much VMEM) fails this at no chip time. Nothing runs, so it says nothing
of results or times. The topology is described inside a fixture (only the
worker that is given this file loads the TPU's library: every kernel's cases
are in this ONE file for that reason) and the tests skip where it cannot
be."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_gdn as gdn
from mxnet_tpu.ops import pallas_grouped_matmul as gmm
from mxnet_tpu.ops import pallas_paged_attention as ppa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _read_selected(q, k_pool, v_pool, pages, starts, counts, position, **kw):
    """``paged_gqa_read`` over a table of selected pages."""
    return ppa.paged_gqa_read(q, k_pool, v_pool, None, position,
                              selected=(pages, starts, counts), **kw)


def _compiled(one_chip, fn, *shapes, donate=()):
    """``fn`` compiled for the described chip at ``shapes`` ((shape, dtype)
    pairs; ``donate``: the arguments given away), the persistent cache off:
    an entry could not be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=donate).lower(*(
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


# run_pages None: the kernel as the cell runs it (a run of RUN_PAGES
# neighbouring pages is one copy of 128 KB from the pool seen as rows); 1: its
# page-by-page walk alone
@pytest.mark.parametrize("run_pages", [None, 1])
@pytest.mark.parametrize("window,pages,columns", [(None, 24576, 640),
                                                  (4096, 12416, 259)])
def test_the_kernel_compiles_for_a_v5e_at_the_cells_widths(one_chip, window,
                                                           pages, columns,
                                                           run_pages):
    compiled = _compiled(
        one_chip,
        lambda q, k, v, t, p: ppa.paged_gqa_read(q, k, v, t, p, window,
                                                 run_pages=run_pages,
                                                 interpret=False),
        ((48, 28, 1, 128), jnp.bfloat16),
        ((pages + 1, 16, 512), jnp.bfloat16),
        ((pages + 1, 16, 512), jnp.bfloat16),
        ((48, columns), jnp.int32), ((48,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    # nothing history-sized beside the pools: the kernel's scratch is VMEM,
    # and the pools' view as rows moves no byte
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# the three expert cells' decode steps and prefill programs: (pairs, d, w,
# experts held): SmallThinker (48 rows of six; its 512-token bucket; a
# 4,096-token block), DeepSeek-V2 (128 rows of six; its 512 bucket),
# dots3-note-prev (48 rows of eight; its 1,024 bucket; a 4,096-token block)
# and, in float32 (the gate's other dtype: blocks twice as large), a decode
# step and a prefill block at the widest widths
@pytest.mark.parametrize("pairs,d,w,held,dtype", [
    (288, 2560, 768, 64, jnp.bfloat16), (3072, 2560, 768, 64, jnp.bfloat16),
    (24576, 2560, 768, 64, jnp.bfloat16), (768, 5120, 1536, 8, jnp.bfloat16),
    (3072, 5120, 1536, 8, jnp.bfloat16), (384, 5120, 1536, 8, jnp.bfloat16),
    (8192, 5120, 1536, 8, jnp.bfloat16), (32768, 5120, 1536, 8, jnp.bfloat16),
    (384, 5120, 1536, 8, jnp.float32), (32768, 5120, 1536, 8, jnp.float32),
    # PR 41: the prefix of the sorted pairs that the round about the products
    # walks: a 4,096-token block of dots3-note-prev (an eighth of 32,768),
    # its decode step (64 of 384), DeepSeek-V2's 1,024 bucket (1,280 of 6,144)
    (4096, 5120, 1536, 8, jnp.bfloat16), (64, 5120, 1536, 8, jnp.bfloat16),
    (1280, 5120, 1536, 8, jnp.bfloat16)])
def test_the_grouped_matmul_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, pairs, d, w, held, dtype):
    compiled = _compiled(
        one_chip,
        lambda x, w_gate, w_up, w_down, sizes: gmm.grouped_glu_ffn(
            x, w_gate, w_up, w_down, sizes, "silu", interpret=False),
        ((pairs, d), dtype), ((held, d, w), dtype), ((held, d, w), dtype),
        ((held, w, d), dtype), ((held,), jnp.int32))
    text = compiled.as_text()
    assert "grouped_matmul_gate_up" in text and "grouped_matmul_down" in text
    # nothing beside the rows between the two calls: no float32 pair, no
    # copy of a layer's weights
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= pairs * w * jnp.dtype(dtype).itemsize + (1 << 20)


@pytest.mark.parametrize("tokens,top_k,experts,prefix", [
    (4096, 8, 256, 4096), (1024, 6, 160, 1280)],
    ids=["dots3_note_block", "deepseek_v2_bucket_1024"])
def test_the_expert_layer_with_its_branch_compiles_for_a_v5e(
        one_chip, monkeypatch, tokens, top_k, experts, prefix):
    """``held_expert_ffn`` with 8 experts held at the cells' widths: the
    gather, the kernels, the mask and the scatter-add inside BOTH branches
    of a ``conditional`` (XLA:TPU's scatter emitter aborts on this
    scatter-add inside a loop body: PERF.md, PR 31), the kernels at the
    prefix's rows and at the whole length, and no more temporaries than the
    whole length's own (the gathered rows, the float32 products, the masked
    products)."""
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    monkeypatch.setattr(gmm, "_resolve_interpret", lambda i: False)
    assert moe.held_prefix_rows(tokens * top_k, 8, experts) == prefix
    d, w, bf16 = 5120, 1536, jnp.bfloat16
    compiled = _compiled(
        one_chip,
        lambda h, router, bias, *mats: moe.held_expert_ffn(
            h, router, *mats, held_experts=range(8), top_k=top_k,
            scoring="sigmoid", router_bias=bias, norm_topk_prob=True,
            count_route=True),
        ((tokens, d), bf16), ((experts, d), jnp.float32),
        ((experts,), jnp.float32), ((8, d, w), bf16), ((8, d, w), bf16),
        ((8, w, d), bf16))
    text = compiled.as_text()
    assert " conditional(" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    pairs = tokens * top_k
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= pairs * d * (2 + 4 + 4) + (64 << 20)


def test_a_programs_expert_layers_share_one_lowering_a_row_count(
        one_chip, monkeypatch):
    """Three expert layers with the branch in one program: three
    ``case`` operations and FOUR Mosaic kernels in the lowered text (gate-up
    and down, at the prefix's rows and at the whole length), not twelve: the
    prefix's products go through the same jitted function, and a lowering
    to Mosaic comes before any compile cache is asked (PERF.md, PR 36)."""
    from mxnet_tpu.parallel import moe

    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    monkeypatch.setattr(gmm, "_resolve_interpret", lambda i: False)

    def layers(h, router, bias, *mats):
        for _ in range(3):
            y, _ = moe.held_expert_ffn(
                h, router, *mats, held_experts=range(8), top_k=8,
                scoring="sigmoid", router_bias=bias, norm_topk_prob=True)
            h = h + y
        return h

    bf16 = jnp.bfloat16
    text = jax.jit(layers).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((512, 256), bf16), ((256, 256), jnp.float32),
            ((256,), jnp.float32), ((8, 256, 128), bf16),
            ((8, 256, 128), bf16), ((8, 128, 256), bf16)))).as_text()
    assert text.count("stablehlo.case") == 3
    assert text.count("tpu_custom_call") == 4


def test_the_grouped_heads_kernel_compiles_at_a_group_of_one(one_chip):
    """Olmo-Hybrid's full layers: 30 query heads over 30 key-value heads of
    128, pools 3,840 wide, rows of up to 4,096 positions."""
    compiled = _compiled(
        one_chip,
        lambda q, k, v, t, p: ppa.paged_gqa_read(q, k, v, t, p,
                                                 interpret=False),
        ((48, 30, 1, 128), jnp.bfloat16), ((3585, 16, 3840), jnp.bfloat16),
        ((3585, 16, 3840), jnp.bfloat16), ((48, 256), jnp.int32),
        ((48,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_gdn_decode_kernel_compiles_for_a_v5e_at_30_x_96_x_192(one_chip):
    """Olmo-Hybrid's linear layers: 48 slots of 30 heads of 96 x 192 float32
    (96 x 5,760 a row: whole (8, 128) tiles, two heads a lane group), the
    state donated: aliased to the output, no copy of it beside the call."""
    f32 = jnp.float32
    state = (48, 96, 30 * 192)
    shapes = [(state, f32), ((48, 30, 96), f32), ((48, 30, 96), f32),
              ((48, 30, 192), f32), ((48, 30), f32), ((48, 30), f32),
              ((48,), jnp.bool_)]
    compiled = _compiled(
        one_chip, lambda s, q, k, v, a, b, live: gdn.gdn_decode_step(
            s, q, k, v, a, b, live, interpret=False), *shapes, donate=(0,))
    assert "gdn_decode_step" in compiled.as_text()
    memory = compiled.memory_analysis()
    state_bytes = 48 * 96 * 5760 * 4
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < 1 << 20


def test_the_lightning_decode_kernel_compiles_for_a_v5e_at_32_x_128_x_128(
        one_chip):
    """MiniCPM-SALA's lightning layers: 48 slots of 32 heads of 128 x 128
    float32 (128 x 4,096 a row, one head a lane group), the kernel without
    its delta term, the state donated: aliased, no copy beside the call."""
    f32 = jnp.float32
    heads = (48, 32, 128)
    shapes = [((48, 128, 32 * 128), f32), (heads, f32), (heads, f32),
              (heads, f32), ((48, 32), f32), ((48, 32), f32),
              ((48,), jnp.bool_)]
    compiled = _compiled(
        one_chip, lambda s, q, k, v, a, b, live: gdn.gdn_decode_step(
            s, q, k, v, a, b, live, interpret=False, delta=False), *shapes,
        donate=(0,))
    assert "lightning_decode_step" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 48 * 128 * 4096 * 4
    assert memory.temp_size_in_bytes < 1 << 20


def test_the_selected_pages_read_compiles_for_a_v5e_at_the_cells_widths(
        one_chip):
    """MiniCPM-SALA's sparse layer: 48 rows, 2 x 16 query heads over 2
    key-value heads of 128, pools of 24,576 pages of 64, tables of 128 pages
    (a row under the dense length lists all it holds): a page's 128 lanes of
    one head are one strided copy; nothing beside the pools."""
    i32 = jnp.int32
    compiled = _compiled(
        one_chip,
        lambda q, k, v, p, s, c, at: _read_selected(
            q, k, v, p, s, c, at, interpret=False),
        ((48, 32, 1, 128), jnp.bfloat16), ((24577, 64, 256), jnp.bfloat16),
        ((24577, 64, 256), jnp.bfloat16), ((48, 2, 128), i32),
        ((48, 2, 128), i32), ((48, 2), i32), ((48,), i32))
    assert "paged_gqa_decode_selected" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_block_scores_kernel_compiles_for_a_v5e_at_the_cells_widths(
        one_chip):
    """The selection's scoring: 48 rows' 4,352 compressed keys of 256
    columns as the page tables of 1,088 pages gathered them (69,632
    positions), a group of 16 query heads a key-value head."""
    compiled = _compiled(
        one_chip,
        lambda q, keys, at: ppa.paged_block_scores(q, keys, at, 32, 16,
                                                   interpret=False),
        ((48, 2, 16, 128), jnp.bfloat16), ((48, 4352, 256), jnp.bfloat16),
        ((48,), jnp.int32))
    assert "paged_block_scores" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens", [16384, 32768, 65536])
def test_the_prefills_scoring_kernel_compiles_for_a_v5e_at_a_buckets_shapes(
        one_chip, tokens):
    """A stretch of 4,096 queries, a group of 16 query heads a key-value
    head, against a selecting bucket's compressed keys (1,023, 2,047, 4,095
    of 32 positions every 16) laid out by phase, the stretch's first position
    a scalar of the running program: a head's keys and a grid step's scores
    stay in VMEM, out come a stretch's pooled block scores and nothing of the
    scores' shape."""
    compiled = _compiled(
        one_chip,
        lambda q, ck, first: ppa.sparse_chunk_scores(
            q, ppa.sparse_chunk_keys(ck, 64, 16), first, 64, 32, 16,
            interpret=False),
        ((4096, 2, 16, 128), jnp.bfloat16),
        ((tokens // 16 - 1, 2, 128), jnp.bfloat16), ((), jnp.int32))
    assert "sparse_chunk_scores" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
