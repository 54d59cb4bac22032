"""The trace reduction on a small trace written out by hand."""
import pytest

from bench import trace as TR

# two prefill programs and one decode program on the device; inside
# them a sparse-FFN kernel, an attention kernel and a fusion each
MODS = [("jit__prefill_blocks_impl", 0, 100, ""),
        ("jit__prefill_block_impl", 200, 100, ""),
        ("jit__decode_impl", 400, 50, "")]
OPS = [("fusion.1", 0, 30, "jit(_prefill_blocks_impl)/dot_general"),
       ("custom-call.3", 30, 50, "jit(_prefill_blocks_impl)/jit(sparse_ffn_batched)/pallas_call"),
       ("custom-call.4", 80, 20, "jit(_prefill_blocks_impl)/jit(flash_attention_rows)/pallas_call"),
       ("custom-call.3", 210, 60, "jit(_prefill_block_impl)/jit(sparse_ffn_batched)/pallas_call"),
       ("fusion.1", 270, 20, "jit(_prefill_block_impl)/dot_general"),
       ("custom-call.9", 400, 40, "jit(_decode_impl)/jit(sparse_ffn_batched)/pallas_call")]
HOST = [("tick", 90, 330, ""), ("readback_prefill", 100, 100, ""),
        ("idle_wait", 300, 90, "")]


def trace():
    return TR.Trace([{TR.MODULES: MODS, TR.OPS: OPS}], HOST, (0, 450))


def test_round_trip_through_json():
    t = trace()
    assert TR.Trace.from_json(t.to_json()) == t


def test_union_counts_overlap_once():
    assert TR.union_ns([("a", 0, 10, ""), ("b", 5, 10, ""),
                        ("c", 20, 5, "")]) == 20


def test_busy_is_the_union_of_operations():
    # ops cover 0-100, 210-290, 400-440
    assert TR.busy_s(trace()) == pytest.approx(220e-9)


def test_modules_by_entry_name():
    prefill = TR.modules(trace(), r"_prefill_blocks?(_paged)?_impl")
    assert [m[1] for m in prefill] == [0, 200]
    assert [m[1] for m in TR.modules(trace(), r"_decode(_paged)?_impl")] \
        == [400]


def test_kernel_time_inside_prefill_only():
    t = trace()
    ffn = TR.within(TR.kernel_events(t, "sparse_ffn_batched"),
                    TR.modules(t, r"_prefill_blocks?(_paged)?_impl"))
    assert sum(e[2] for e in ffn) == 110
    attn = TR.kernel_events(t, "flash_attention_rows")
    assert [e[2] for e in attn] == [20]


def test_idle_gaps_named_by_the_host():
    # gaps: 100-210 (mid 155: tick + readback_prefill -> readback),
    # 290-400 (mid 345: tick + idle_wait -> idle_wait)
    gaps = TR.idle_gaps(trace())
    assert gaps == [["readback_prefill", pytest.approx(110e-9)],
                    ["idle_wait", pytest.approx(110e-9)]]


def test_top_ops_sum_by_name():
    top = TR.top_ops(trace(), 2)
    assert top[0][0] == "custom-call.3"
    assert top[0][1] == pytest.approx(110e-9)


def test_describe_counts_lines_and_programs():
    d = TR.describe(trace())
    assert d["chips"] == 1
    assert d["events"] == [{TR.MODULES: 3, TR.OPS: 6}]
    assert d["host"] == {"tick": 1, "readback_prefill": 1, "idle_wait": 1}
    assert d["modules"][0] == ("jit__prefill_blocks_impl", 1)


def test_kernel_is_its_custom_call_not_its_wrapper_or_consumers():
    # TPU traces name an operation by its HLO text: the kernel's custom
    # call is `%<kernel>.<n>`; the wrapper's transposes carry its jit in
    # their source op, and a consumer names it as an operand
    ops = [("%copy_bitcast_fusion = bf16[2,4,128,128] fusion(%q.1)", 0, 5,
            "jit(_prefill_blocks_impl)/jit(flash_attention_rows)/transpose"),
           ("%flash_attention_rows.12.clone = f32[2,4,128,128] "
            "custom-call(%p.1, %l.1, %copy_bitcast_fusion)", 5, 20, ""),
           ("%bitcast.3 = f32[2,128,4,128] bitcast(%flash_attention_rows"
            ".12.clone)", 25, 1, ""),
           ("%convert_fusion.2 = bf16[1,128,4096] fusion(%sparse_ffn_"
            "batched.4)", 30, 2, "jit(_prefill_block_impl)/convert"),
           ("sparse_ffn_batched.4", 40, 30,
            "jit(_prefill_block_impl)/jit(sparse_ffn_batched)/pallas_call"),
           ("%sparse_ffn.2 = f32[1,128,4096] custom-call(%x)", 80, 9, "")]
    t = TR.Trace([{TR.MODULES: [], TR.OPS: ops}], [], (0, 100))
    assert [e[1] for e in TR.kernel_events(t, "flash_attention_rows")] \
        == [5]
    assert [e[1] for e in TR.kernel_events(t, "sparse_ffn_batched")] \
        == [40]
    assert [e[1] for e in TR.kernel_events(t, "sparse_ffn")] == [80]


def test_operands_in_on_chip_memory_from_the_long_name():
    name = ("%sparse_ffn_batched.3 = f32[1,128,4096]{2,1,0:T(8,128)} "
            "custom-call(s32[1,56]{1,0:T(1,128)} %bitcast.199, s32[1]{0:"
            "T(128)} %constant.110, bf16[1,128,4096]{2,1,0:T(8,128)(2,1)} "
            "%gte.277, bf16[4096,14336]{1,0:T(8,128)(2,1)} %gte.278, "
            "bf16[4096,14336]{1,0:T(8,128)(2,1)S(1)} %gte.279, "
            "bf16[14336,4096]{1,0:T(8,128)(2,1)} %gte.280), custom_call_"
            "target=\"tpu_custom_call\", operand_layout_constraints={s32"
            "[1,56]{1,0}, s32[1]{0}, bf16[1,128,4096]{2,1,0}}")
    ops = ("ids", "cnt", "x", "wg", "wu", "wd")
    assert TR.on_chip_operands(name, ops) == {"wu"}
    assert TR.on_chip_operands(name, ops[:5]) is None   # count differs
    assert TR.on_chip_operands("sparse_ffn_batched.3", ops) is None


def test_kernel_roofline_pairs_calls_with_program_executions():
    import types
    from bench import flops as FL
    shapes = types.SimpleNamespace(L=2, N=128)
    work = lambda s, rows, on_chip, layer: (
        0.0, 1e3 * len(rows) * (0.5 if "wu" in on_chip else 1))
    call = lambda rows: types.SimpleNamespace(kind="prefill", rows=rows)
    sparse, dense = [(0, 128, False, False)], [(0, 128, True, False)]
    mods = [("jit__prefill_block_impl", 0, 100, ""),
            ("jit__prefill_block_impl", 200, 100, "")]
    k = "%sparse_ffn_batched.3 = f32[1]{0} custom-call(bf16[1]{0} %x)"
    ops = [(k, 10, 20, ""), (k, 40, 20, "")]      # first call only
    ctx = types.SimpleNamespace(
        trace=TR.Trace([{TR.MODULES: mods, TR.OPS: ops}], [], (0, 300)),
        traced_calls=[call(sparse), call(dense)], shapes=shapes,
        peak={"flops_bf16": 1.0, "hbm_bytes_s": 1e12})
    runs = lambda rows: any(not r[2] for r in rows)
    read = lambda: TR.kernel_roofline(ctx, r"_prefill_block_impl",
                                      "sparse_ffn_batched", ("x",), work,
                                      runs)
    # least 1 ns per execution, 2 executions over 40 ns
    assert read() == pytest.approx(5.0)
    ctx.traced_calls = [call(sparse), call(sparse)]   # second lacks both
    assert read() is None
    ctx.traced_calls = [call(sparse)]                 # one call, two runs
    assert read() is None
    assert FL.least_time(0.0, 1e3, ctx.peak) == pytest.approx(1e-9)
