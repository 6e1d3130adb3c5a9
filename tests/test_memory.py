"""Buffer-liveness & peak-residency analysis (ISSUE 12, docs/ANALYSIS.md
"Memory"): the liveness engine on synthetic HLO in both dialects — tuple
result sizing, donated-alias exclusion, timeline peak position, every
materialization detector firing AND staying quiet on the fixed program —
plus live cross-validation of ``audit(...).memory`` against
``jax.stages.Compiled.memory_analysis()`` on CPU-compiled step/decode
programs within the documented tolerance."""
import numpy as np
import pytest

from mxnet_tpu.analysis import (VALIDATION_TOLERANCE, audit_text,
                                jax_expected_peak, memory_report)

# ---------------------------------------------------------------------------
# synthetic programs, compiled (hlo) dialect — scheduled text
# ---------------------------------------------------------------------------

_PEAK_HLO = """\
HloModule t, is_scheduled=true

ENTRY %main.9 (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  %a.2 = f32[256]{0} broadcast(f32[4]{0} %p0.1), dimensions={0}
  %b.3 = f32[1024]{0} broadcast(f32[256]{0} %a.2), dimensions={0}
  %c.4 = f32[4]{0} slice(f32[1024]{0} %b.3), slice={[0:4]}
  ROOT %d.5 = f32[4]{0} add(f32[4]{0} %c.4, f32[4]{0} %p0.1)
}
"""


def test_hlo_timeline_peak_position():
    """The peak lands where both broadcasts coexist — instruction 3 — and
    the timeline drops once the 1 KiB temp dies."""
    rep = audit_text(_PEAK_HLO)
    assert rep.dialect == "hlo"
    mem = memory_report(rep)
    # at %b.3: pinned 16 + a (1024) + b (4096)
    assert mem.peak_bytes == 16 + 1024 + 4096
    assert mem.peak_line == 6  # the %b.3 line
    assert mem.input_bytes == 16
    # timeline entries are (line, total, non-input); after %b.3 the first
    # broadcast is dead
    totals = {line: tot for line, tot, _ in mem.timeline}
    assert totals[7] == 16 + 4096 + 16  # %c.4: b + c + pinned
    big = mem.largest_buffers(1)[0]
    assert big.op == "broadcast" and big.bytes == 4096


_TUPLE_HLO = """\
HloModule t, is_scheduled=true

ENTRY %main.9 (p0.1: f32[1024]) -> f32[1024] {
  %p0.1 = f32[1024]{0} parameter(0)
  %ar.2 = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(f32[1024]{0} %p0.1), replica_groups={{0,1}}, to_apply=%add
  %ard.3 = f32[1024]{0} all-reduce-done((f32[1024]{0}, f32[1024]{0}) %ar.2)
  ROOT %e.4 = f32[1024]{0} exponential(f32[1024]{0} %ard.3)
}
"""


def test_tuple_result_op_sizing_and_async_done_zero_cost():
    """A tuple-result async start sums every element; the -done half is a
    zero-cost alias (one allocation per async pair, matching the census's
    one-collective-per-pair rule)."""
    rep = audit_text(_TUPLE_HLO)
    start = [v for v in rep.values if v.op == "all_reduce"]
    assert len(start) == 1 and start[0].bytes == 8192
    assert len(start[0].results) == 2
    done = [v for v in rep.values if v.op == "all_reduce_done"]
    assert len(done) == 1
    mem = memory_report(rep)
    # peak at the start op: pinned 4096 + the 8192 B result tuple; the
    # done op and the downstream exp must not push it higher (the done is
    # an alias, and the tuple is dead by the time exp's 4096 B exists)
    assert mem.peak_bytes == 4096 + 8192
    assert mem.peak_line == 5
    assert all(b.op != "all_reduce_done" for b in mem.buffers)


_DONATED_HLO = """\
HloModule t, is_scheduled=true, input_output_alias={ {1}: (0, {}, may-alias) }

ENTRY %main.9 (p0.1: f32[1024], p1.2: f32[1024]) -> (f32[], f32[1024]) {
  %p0.1 = f32[1024]{0} parameter(0)
  %p1.2 = f32[1024]{0} parameter(1)
  %upd.3 = f32[1024]{0} add(f32[1024]{0} %p0.1, f32[1024]{0} %p1.2)
  %s.4 = f32[] constant(0)
  ROOT %t.5 = (f32[], f32[1024]{0}) tuple(f32[] %s.4, f32[1024]{0} %upd.3)
}
"""


def test_donated_alias_exclusion_hlo():
    """The donated carry's output writes the input buffer in place: with
    the alias header the update costs zero extra bytes, without it the
    same program carries a second copy of the tensor."""
    rep = audit_text(_DONATED_HLO)
    assert rep.donation.out_alias == {1: 0}
    mem = memory_report(rep)
    plain = memory_report(audit_text(
        _DONATED_HLO.replace(", input_output_alias="
                             "{ {1}: (0, {}, may-alias) }", "")))
    assert plain.peak_bytes - mem.peak_bytes == 4096
    assert mem.donated_bytes == 4096
    assert plain.donated_bytes == 0
    assert mem.peak_bytes == 8192 + 4  # two pinned params + the scalar


def test_single_output_donation_alias_key():
    """A single-(non-tuple)-output donated program spells the alias key
    `{}` (the empty index path) — it must still parse as output 0, or
    donation reads 0% and the donated buffer is double-counted (review
    regression of the ISSUE 12 out_alias capture)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.analysis import audit_compiled

    co = jax.jit(lambda x: x + 1.0, donate_argnums=0).lower(
        jnp.ones((256,))).compile()
    rep = audit_compiled(co)
    assert rep.donation.aliased == {0: "may-alias"}
    assert rep.donation.out_alias == {0: 0}
    mem = memory_report(rep)
    assert mem.donated_bytes == 1024
    want = jax_expected_peak(co.memory_analysis())
    assert abs(mem.peak_bytes - want) / want <= VALIDATION_TOLERANCE


_DONATED_MLIR = """\
module @jit_t attributes {mhlo.num_partitions = 1 : i32} {
  func.func public @main(%arg0: tensor<1024xf32> {tf.aliasing_output = 1 : i32}, %arg1: tensor<1024xf32>) -> (tensor<f32>, tensor<1024xf32>) {
    %0 = stablehlo.add %arg0, %arg1 : tensor<1024xf32>
    %cst = stablehlo.constant dense<0.000000e+00> : tensor<f32>
    return %cst, %0 : tensor<f32>, tensor<1024xf32>
  }
}
"""


def test_both_dialects_agree_on_donated_program():
    """The same donated-update program in the lowered dialect produces
    the same residency estimate as the compiled spelling above."""
    rep = audit_text(_DONATED_MLIR)
    assert rep.dialect == "stablehlo"
    assert rep.donation.out_alias == {1: 0}
    assert rep.output_ids == ("cst", "0")
    mem = memory_report(rep)
    hlo = memory_report(audit_text(_DONATED_HLO))
    assert mem.peak_bytes == hlo.peak_bytes == 8192 + 4
    assert mem.donated_bytes == hlo.donated_bytes == 4096


def test_category_attribution_at_peak():
    cats = {0: "params", 1: "batch"}
    mem = memory_report(audit_text(_DONATED_HLO), categories=cats,
                        default_category="activations")
    assert mem.by_category["params"] == 4096
    assert mem.by_category["batch"] == 4096
    # the aliased update costs nothing, only the scalar constant remains
    assert mem.by_category.get("activations", 0) == 4
    assert mem.category_share("params") == pytest.approx(
        4096 / mem.peak_bytes)


# ---------------------------------------------------------------------------
# materialization detectors
# ---------------------------------------------------------------------------

_GATHER_HLO = """\
HloModule t, is_scheduled=true

ENTRY %main.9 (pool.1: f32[64,16], idx.2: s32[56,1]) -> f32[56,16] {
  %pool.1 = f32[64,16]{1,0} parameter(0)
  %idx.2 = s32[56,1]{1,0} parameter(1)
  ROOT %g.3 = f32[56,16]{1,0} gather(f32[64,16]{1,0} %pool.1, s32[56,1]{1,0} %idx.2), offset_dims={1}
}
"""


def test_kv_gather_materialize_fires_and_stays_quiet():
    """A gather whose result is pool-sized fires against KV-categorized
    inputs; a small row-gather of the same pool — and the identical
    program without KV categories — stay quiet."""
    rep = audit_text(_GATHER_HLO)
    mem = memory_report(rep, categories={0: "kv_pages"})
    assert mem.materialization_kinds() == {"kv_gather_materialize": 1}
    assert "gather materializes" in str(mem.materializations[0])
    # no KV category -> not a KV pool, no flag
    quiet = memory_report(rep)
    assert quiet.materializations == []
    # fixed program: a per-row gather far below the pool size
    fixed = _GATHER_HLO.replace("f32[56,16]{1,0} gather",
                                "f32[4,16]{1,0} gather") \
                       .replace("-> f32[56,16]", "-> f32[4,16]") \
                       .replace("s32[56,1]", "s32[4,1]")
    mem2 = memory_report(audit_text(fixed), categories={0: "kv_pages"})
    assert mem2.materializations == []


_UPCAST_HLO = """\
HloModule t, is_scheduled=true

ENTRY %main.9 (p0.1: bf16[1048576]) -> f32[1048576] {
  %p0.1 = bf16[1048576]{0} parameter(0)
  ROOT %c.2 = f32[1048576]{0} convert(bf16[1048576]{0} %p0.1)
}
"""


def test_f32_upcast_detector_fires_and_respects_floor():
    """A 4 MiB f32 copy of a bf16-stored tensor fires; the same convert
    below the 1 MiB floor (a tiny CI program) stays quiet."""
    mem = memory_report(audit_text(_UPCAST_HLO))
    assert mem.materialization_kinds() == {"f32_upcast": 1}
    small = _UPCAST_HLO.replace("1048576", "1024")
    assert memory_report(audit_text(small)).materializations == []


def _long_lived_program(early_use: bool) -> str:
    """~20 instructions; a 4 MiB broadcast defined up front is consumed
    either at the end (remat-defeating) or immediately (fixed)."""
    mid = "\n".join(
        f"  %n{i} = f32[4]{{0}} add(f32[4]{{0}} %p0.1, f32[4]{{0}} %p0.1)"
        for i in range(16))
    use_line = ("  %u.9 = f32[4]{0} slice(f32[1048576]{0} %big.2), "
                "slice={[0:4]}")
    if early_use:
        body = f"{use_line}\n{mid}"
    else:
        body = f"{mid}\n{use_line}"
    return f"""\
HloModule t, is_scheduled=true

ENTRY %main.9 (p0.1: f32[4]) -> f32[4] {{
  %p0.1 = f32[4]{{0}} parameter(0)
  %big.2 = f32[1048576]{{0}} broadcast(f32[4]{{0}} %p0.1), dimensions={{0}}
{body}
  ROOT %d.5 = f32[4]{{0}} add(f32[4]{{0}} %u.9, f32[4]{{0}} %p0.1)
}}
"""


def test_long_lived_temp_detector():
    """A 4 MiB buffer held across most of the program is flagged as a
    remat-defeating live range; consumed immediately it is not."""
    mem = memory_report(audit_text(_long_lived_program(early_use=False)))
    assert "long_lived_temp" in mem.materialization_kinds()
    mem2 = memory_report(audit_text(_long_lived_program(early_use=True)))
    assert mem2.materializations == []


# ---------------------------------------------------------------------------
# live programs: cross-validation + category truth
# ---------------------------------------------------------------------------

def _mlp_step():
    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    ts = TrainStep(net, lambda o, *l: ((o - l[0]) ** 2).mean(),
                   optimizer.Adam(learning_rate=1e-3))
    return ts, (x, nd.zeros((8, 8)))


def test_step_peak_matches_memory_analysis():
    """ISSUE 12 acceptance: MemoryReport.peak_bytes agrees with
    memory_analysis() on the CPU-compiled step within the documented
    tolerance."""
    ts, batch = _mlp_step()
    audit = ts.audit(*batch)
    mem = audit.memory
    ma = ts.lower_hlo(*batch).compile().memory_analysis()
    want = jax_expected_peak(ma)
    assert want > 0
    err = abs(mem.peak_bytes - want) / want
    assert err <= VALIDATION_TOLERANCE, \
        f"step peak {mem.peak_bytes} vs memory_analysis {want} ({err:.1%})"
    # carry categories: params + opt_state leaves, batch arrays
    assert mem.by_category["params"] > 0
    assert mem.by_category["opt_state"] > mem.by_category["params"]
    assert mem.by_category["batch"] > 0
    # Adam's fully donated carry: params + both moments write in place
    assert mem.donated_bytes == \
        mem.by_category["params"] + mem.by_category["opt_state"]


def test_window_audit_carries_memory_report():
    ts, batch = _mlp_step()
    mem = ts.audit(*batch, window=2).memory
    assert mem is not None and mem.peak_bytes > 0
    assert mem.by_category["opt_state"] > 0
    # the fused window threads the stacked batch through the scan carry —
    # liveness must not double-count it (pass-through aliasing)
    assert mem.by_category["batch"] >= 2 * \
        ts.audit(*batch).memory.by_category["batch"] - 8


@pytest.fixture(scope="module")
def engines():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.inference import GenerationEngine
    from mxnet_tpu.models import gpt2

    mx.random.seed(0)
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2, units=32,
                        num_heads=2, max_length=64, vocab_size=64)
    net.initialize()
    _ = net(nd.array(np.zeros((1, 4), np.int32)))
    dense = GenerationEngine(net, batch_size=2, max_length=64,
                             prefill_buckets=(8, 16))
    paged = GenerationEngine(net, batch_size=2, max_length=64,
                             prefill_buckets=(8, 16), paged=True,
                             page_size=16)
    return dense, paged


def test_decode_peak_matches_memory_analysis(engines):
    import jax
    import jax.numpy as jnp

    dense, _ = engines
    mem = dense.audit().memory
    lo = dense._decode_jit.lower(
        dense._params(), dense.cache, jnp.asarray(dense.last_tokens),
        jnp.asarray(dense.positions), jnp.asarray(dense.done),
        jax.random.key(0))
    want = jax_expected_peak(lo.compile().memory_analysis())
    err = abs(mem.peak_bytes - want) / want
    assert err <= VALIDATION_TOLERANCE, \
        f"decode peak {mem.peak_bytes} vs memory_analysis {want} ({err:.1%})"


def test_dense_decode_kv_category_and_no_materializations(engines):
    dense, _ = engines
    mem = dense.audit().memory
    assert mem.by_category["kv_cache"] == \
        int(sum(b.nbytes for layer in dense.cache for b in layer))
    assert mem.materializations == []   # dense reads the cache in place


def test_paged_decode_kv_pages_attribution_and_gather_detector(engines):
    """The paged decode's pool+table bytes are auditor-attributed exactly
    and the compiled program is gather-free where the paged attention
    kernel reads the pools (ISSUE 18; on the CPU the operator takes the XLA
    path, so the gate's backend check is patched and the kernel traced
    interpreted) — while the detector still proves it would catch the pool
    gather if the kernel were bypassed (knob off: one gather per K/V pool
    per layer, as before the kernel existed)."""
    from conftest import load_tool
    from mxnet_tpu import config as _config

    # the gates' own builders: one definition of "the paged program as the
    # chip runs it"
    fams = load_tool("families").load()

    _, paged = engines
    mem = paged.audit().memory
    hand = int(sum(b.nbytes for layer in paged.pools for b in layer)) \
        + int(paged.page_table.nbytes)
    assert mem.by_category["kv_pages"] == hand
    # two heads of 64 fill a lane tile: the kernel's gate passes, and its
    # decode program holds no gather of the pool
    with fams.kernel_traced():
        kernel = fams.paged_engine()
        assert kernel.read_path == "pallas_paged_kernel"
        kinds = kernel.audit().memory.materialization_kinds()
    assert kinds.get("kv_gather_materialize", 0) == 0
    # a FRESH engine with the kernel knob off re-traces the gather path
    # (the knob is trace-time; an existing engine's decode jaxpr is cached,
    # so toggling it on `paged` would silently audit the old trace)
    _config.set("paged_attention_kernel", False)
    try:
        kinds = fams.paged_engine(32).audit().memory.materialization_kinds()
    finally:
        _config.set("paged_attention_kernel", True)
    assert kinds.get("kv_gather_materialize") == 4  # 2 layers x (K, V)


def test_prefill_audit_memory(engines):
    dense, _ = engines
    mem = dense.audit(bucket=8).memory
    assert mem.peak_bytes > mem.input_bytes  # prefill materializes temps
    assert mem.by_category["params"] > 0


def test_scan_lowered_dialect_subcomputation_recursion():
    """The lowered dialect's func.call scan body contributes its internal
    working set at the call point (recursion through subcomputations)."""
    import jax
    import jax.numpy as jnp

    def step(c, x):
        return jnp.tanh(c @ x), c.sum()

    def f(c, xs):
        return jax.lax.scan(step, c, xs)

    lo = jax.jit(f, donate_argnums=(0,)).lower(
        jnp.ones((64, 64)), jnp.ones((8, 64, 64)))
    from mxnet_tpu.analysis import audit_lowered

    rep = audit_lowered(lo)
    assert rep.subcomputations          # the private scan-body func
    mem = memory_report(rep)
    # the body's dot result (64x64 f32) must show up beyond the pinned
    # inputs — without recursion the while body would look free
    assert mem.temp_peak_bytes >= 64 * 64 * 4
