"""The trace reduction, on intervals made up by hand and on a small trace
recorded on the CPU."""
import numpy as np
import pytest

from bench import trace_reduce
from bench.trace_reduce import Reduced, _Union


def test_union_counts_overlap_once():
    u = _Union(np.array([0.0, 5.0, 2.0, 20.0]), np.array([4.0, 8.0, 3.0, 25.0]))
    assert list(u.a) == [0.0, 5.0, 20.0] and list(u.b) == [4.0, 8.0, 25.0]
    assert u.covered([0.0], [100.0]) == 12.0
    assert u.covered([3.0, 21.0], [6.0, 22.0]) == 3.0
    gs, ge = u.gaps(0.0, 30.0)
    assert list(zip(gs, ge)) == [(4.0, 5.0), (8.0, 20.0), (25.0, 30.0)]


def test_reduced_by_hand():
    ops = {0: (np.array([10.0, 30.0, 60.0]), np.array([20.0, 50.0, 70.0]),
               ["fusion.1", "psum.2", "fusion.1"],
               ["fusion", "all-reduce", "fusion"]),
           1: (np.array([10.0, 30.0]), np.array([30.0, 40.0]),
               ["fusion.1", "psum.2"], ["fusion", "all-reduce"])}
    spans = {"bench.window": (np.array([0.0]), np.array([100.0])),
             "bench.decode": (np.array([5.0, 55.0]), np.array([52.0, 75.0])),
             "bench.step": (np.array([0.0]), np.array([80.0]))}
    r = Reduced(ops, spans, (0.0, 100.0))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s() == pytest.approx(np.mean([40.0, 30.0]) * 1e-9)
    assert r.busy_s("bench.decode") == pytest.approx(35.0 * 1e-9)
    assert r.count("bench.decode") == 2
    assert r.op_s(("all-reduce",), "bench.decode") == pytest.approx(15e-9)
    assert r.op_s(("no-such-op",)) is None
    top = r.top_ops(1)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(20e-9)
    gaps = r.idle_gaps(2)
    assert gaps[0] == ["outside_step", pytest.approx(30e-9)]   # 70..100
    assert gaps[1][0] == "decode"                                # 0..10: step
    assert r.label(25.0) == "decode" and r.label(78.0) == "step"


def test_names_and_opcodes():
    ev = ("%psum.40 = bf16[16,1,4096]{2,0,1:T(8,128)(2,1)S(1)} "
          "all-reduce(%fusion.175), channel_id=1, replica_groups={{0,1,2,3}}")
    assert trace_reduce.short_name(ev) == "psum.40"
    assert trace_reduce.opcode(ev) == "all-reduce"
    ev = ("%copy-start.2 = (bf16[16,1,4096]{2,0,1:T(8,128)(2,1)}, "
          "u32[]{:S(2)}) copy-start(bf16[16,1,4096]{2,0,1} %x)")
    assert trace_reduce.opcode(ev) == "copy-start"
    ev = "%while.13 = (s32[]{:T(128)}, bf16[8,1,2048]{2,0,1}) while(%t)"
    assert trace_reduce.opcode(ev) == "while"
    assert trace_reduce.opcode("dot_general.1") == "dot_general.1"


def test_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.decode"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path is not None
    r = trace_reduce.reduce(path)
    assert r.count("bench.decode") == 3
    assert r.devices, "no device operations found"
    busy = r.busy_s()
    assert 0 < busy <= r.window_s
    assert r.busy_s("bench.decode") <= busy + 1e-12
    names = [n for n, _ in r.top_ops(10)]
    assert any("dot" in n or "fusion" in n or "tanh" in n for n in names)
    assert all(s >= 0 for _, s in r.idle_gaps(10))
