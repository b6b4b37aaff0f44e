"""The reduction from a trace to numbers, on a small synthetic trace."""
import trace_reduce as tr

HOST = [
    ("bench:query", 0, 1000), ("bench:execute", 0, 800), ("Pjit", 100, 300),
    ("bench:materialize", 800, 1000),
    ("bench:query", 1100, 2000), ("bench:execute", 1100, 1900),
    ("bench:materialize", 1900, 2000),
]
PLANES = {
    "/host:CPU": {"python3": HOST, "other": [("noise", 0, 5000)]},
    "/device:TPU:0": {
        "XLA Ops": [("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop", 200, 500),
                    ("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %b), kind=kLoop", 400, 600),
                    ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop", 1200, 1500),
                    ("%late = s32[8]{0} copy(s32[8]{0} %c)", 5000, 6000)],
        "XLA Modules": [("jit_f", 200, 600), ("jit_f", 1200, 1500),
                        ("jit_late", 5000, 6000)],
    },
}


def test_busy_is_the_union_inside_the_traced_queries():
    r = tr.reduce(PLANES)
    assert r["queries"] == 2 and r["chips"] == 1
    assert r["window_s"] == 2000e-9
    assert abs(r["busy_s"] - 700e-9) < 1e-15       # 200..600 and 1200..1500
    assert r["modules"] == 2                       # the late one is outside


def test_per_query_split_and_idle_share():
    r = tr.reduce(PLANES)
    assert abs(r["busy_s"] / r["queries"] - 350e-9) < 1e-15
    assert abs(1 - r["busy_s"] / r["window_s"] - 0.65) < 1e-9


def test_idle_gaps_carry_the_phase_and_the_innermost_host_event():
    gaps = dict(tr.reduce(PLANES)["idle_gaps"])
    assert abs(gaps["execute/Pjit"] - 200e-9) < 1e-15     # 0..200, middle 100
    assert abs(gaps["materialize"] - 600e-9) < 1e-15      # 600..1200, middle 900
    assert abs(gaps["execute"] - 500e-9) < 1e-15          # 1500..2000
    assert abs(sum(gaps.values()) - 1300e-9) < 1e-15


def test_device_ops_are_named_short_and_summed():
    ops = dict(tr.reduce(PLANES)["device_ops"])
    assert abs(ops["%fusion.1 fusion s32[8]"] - 600e-9) < 1e-15
    assert abs(ops["%fusion.2 fusion s32[8]"] - 200e-9) < 1e-15
    assert tr.short_name(
        '%custom-call.7 = u32[64]{0:T(1024)} custom-call(s64[64]{0} %x), '
        'custom_call_target="X64SplitLow"') == "%custom-call.7 custom-call X64SplitLow u32[64]"
    assert tr.short_name("jit_raw(123)") == "jit_raw(123)"


def test_no_annotations_no_reduction_and_no_device_no_device_numbers():
    assert tr.reduce({"/host:CPU": {"t": [("x", 0, 1)]}}) is None
    r = tr.reduce({"/host:CPU": {"python3": HOST}})
    assert r["queries"] == 2 and r["busy_s"] is None and r["device_ops"] == []


def test_union():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
