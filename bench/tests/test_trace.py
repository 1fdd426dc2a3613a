"""The trace reduction on a small synthetic trace."""

from bench import trace
from bench.trace import Event


def _trace():
    # device 0: a step module [100, 400) holding a sort, the kernel and a
    # scatter; a second step [600, 800); an op outside any module
    d0 = {"modules": [Event("jit_fused_fn", 100, 300),
                      Event("jit_fused_fn", 600, 200)],
          "ops": [Event("sort.1", 100, 50), Event("fusion.2", 140, 30),
                  Event("kernel", 170, 200), Event("scatter.3", 370, 30),
                  Event("sort.1", 600, 40), Event("kernel", 640, 160),
                  Event("copy", 900, 20)]}
    d1 = {"modules": [Event("jit_fused_fn", 100, 100)],
          "ops": [Event("kernel", 100, 100)]}
    host = {"python": [Event("engine.serve_stream", 0, 1000),
                       Event("bench.generate", 420, 100),
                       Event("fetch", 820, 50)]}
    return trace.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, host)


def test_merge_and_busy_union():
    assert trace.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    t = _trace()
    # overlapping sort/fusion count once: [100,400) + [600,800) + [900,920)
    assert trace.busy_ns(t.devices["/device:TPU:0"]["ops"]) == 520
    assert trace.busy_ns(t.devices["/device:TPU:1"]["ops"]) == 100


def test_per_name_and_module_sums():
    s = trace.summarize_device(_trace().devices["/device:TPU:0"])
    assert s.op_ns["kernel"] == 360 and s.op_count["kernel"] == 2
    assert s.op_ns["sort.1"] == 90
    assert s.module_ns == {"jit_fused_fn": 500}
    assert s.module_count == {"jit_fused_fn": 2}
    assert s.module_op_ns["jit_fused_fn"]["kernel"] == 360
    assert s.module_op_ns[None] == {"copy": 20}


def test_idle_gaps_named_by_innermost_host_span():
    assert trace.gaps([Event("a", 10, 10), Event("b", 15, 10)], 0, 40) == \
        [(0, 10), (25, 40)]
    red = trace.reduce(_trace())
    # device 0 idles [400,600) under bench.generate at 500 and [800,900)
    # under fetch at 850; device 1 idles [200,920) under serve_stream;
    # gaps are averaged over the two devices
    assert red.idle_gaps_ns == {"bench.generate": 100.0, "fetch": 50.0,
                                "engine.serve_stream": 360.0}
    assert red.mean(lambda d: d.busy_ns) == 310.0


def test_innermost_stack():
    spans = [Event("outer", 0, 100), Event("mid", 10, 50),
             Event("inner", 20, 10), Event("later", 70, 10)]
    assert trace.innermost(spans, [5, 25, 45, 75, 95, 150]) == \
        ["outer", "inner", "mid", "later", "outer", None]


def test_top():
    assert trace.top({"a": 3e9, "b": 1e9, "c": 2e9}, n=2) == \
        [["a", 3.0], ["c", 2.0]]


def test_short_op_names():
    hlo = ("%fused_flow_serve_padded.1 = (f32[512,128]{1,0:T(8,128)S(1)}, "
           "s32[512,1]{1,0:T(8,128)S(1)}) custom-call(s32[2]{0} %p)")
    assert trace.short_name(hlo) == "fused_flow_serve_padded.1 f32[512,128]"
    assert trace.short_name("%fusion.11 = f32[1048576,28]{0,1:T(8,128)} "
                            "fusion(%a), kind=kCustom") == \
        "fusion.11 f32[1048576,28]"
    assert trace.short_name("kernel") == "kernel"
