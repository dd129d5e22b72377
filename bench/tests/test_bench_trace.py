"""The trace reduction on synthetic and recorded traces."""
import pytest

from bench import trace


def test_busy_union_gaps_and_modules():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1), ("e", 50, 10)]
    assert trace.busy_ns(ops) == 15 + 5 + 10
    assert trace.gaps(ops) == [(15, 15), (35, 15)]
    mods = [("jit_minhash_pallas(12)", 0, 4e9), ("jit_score_one(3)", 5, 1e9),
            ("jit_minhash_pallas(12)", 9, 2e9)]
    assert trace.module_seconds(mods) == {"jit_minhash_pallas": 6.0, "jit_score_one": 1.0}


def test_reduce_idle_share_and_named_gaps():
    dev = {"/device:TPU:0": {trace.OPS_LINE: [("fusion.1", 0, 1e9), ("fusion.2", 3e9, 1e9)],
                             trace.MODULES_LINE: [("jit_score_one(1)", 0, 1e9),
                                                  ("jit_minhash_pallas(2)", 3e9, 1e9)]}}
    host = [("bench.pipeline", 0, 4e9), ("lm_tokenize", 1.5e9, 1e9)]
    s = trace.reduce(dev, host, window_s=5.0)
    assert s["busy_s"] == pytest.approx(2.0)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.6)
    assert trace.module_time(s, "jit_minhash_pallas") == pytest.approx(1.0)
    assert trace.module_time(s, "jit_nothing") is None
    assert s["gaps"] == [["lm_tokenize", pytest.approx(2.0)], ["host idle", pytest.approx(1.0)]]
    assert s["ops"] == [["jit_score_one/fusion", pytest.approx(1.0)],
                        ["jit_minhash_pallas/fusion", pytest.approx(1.0)]]


def test_op_kind_is_the_hlo_opcode():
    assert trace.op_kind("%while.10 = (s32[]{:T(128)}, bf16[64,2]{1,0:T(8,128)(2,1)S(1)}) "
                         "while((s32[], bf16[64,2]) %tuple.37), condition=%c") == "while"
    assert trace.op_kind("%branch_0_fun.1 = u32[4096,256]{1,0:T(8,128)} custom-call(u32[4096,512] "
                         "%copy-done)") == "custom-call"
    assert trace.op_kind("fusion.3") == "fusion"


def test_reduce_without_device_planes():
    s = trace.reduce({}, [], window_s=1.0)
    assert s["busy_s"] == 0.0 and s["gaps"] == []


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.recorded"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = trace.load(str(tmp_path))
    assert devices == {}  # no TPU plane on the CPU
    assert any(name == "bench.recorded" for name, _, _ in host)
