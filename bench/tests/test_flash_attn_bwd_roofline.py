"""The reader of ``flash_attn_bwd_roofline`` on traces made by hand: the
least time of a causal backward per dk/dv call over the device time of
both backward kernels, nothing where no backward kernel ran, and the
forward kernel and the operations that read a kernel's result left out."""
from types import SimpleNamespace

import pytest

import conftest
from harness import spec
from harness.counts import causal_attention_fwd
from harness.trace import Event, Reduced

DEV = "/device:TPU:0"
CONFIG = {"hidden_size": 576, "num_attention_heads": 9}
TRAFFIC = {"batch": 8, "seq": 2048}


def op(name, dur_ns):
    return Event(DEV, "XLA Ops", name, 0.0, dur_ns)


def read(*ops):
    ctx = SimpleNamespace(
        config=CONFIG, traffic=TRAFFIC, peak=conftest.CPU_PEAK,
        trace=Reduced(window_s=1.0, busy_s=0.5, devices=1, ops=list(ops)),
        info={})
    return spec.reader("flash_attn_bwd_roofline").read(ctx)


def least_s():
    ops, nbytes = causal_attention_fwd(8, 9, 2048, 64)
    return max(2.5 * ops / 1e12, 2 * nbytes / 1e11)


def test_reads_the_least_time_over_both_kernels():
    got = read(
        op("%flash_attention_bwd_dkv.3 = (f32[8,3,2048,64]", 4e6),
        op("%flash_attention_bwd_dq.3 = f32[8,9,2048,64]", 2e6),
        op("%flash_attention_bwd_dkv.4 = (f32[8,3,2048,64]", 5e6),
        op("%flash_attention_bwd_dq.4 = f32[8,9,2048,64]", 3e6))
    assert got == pytest.approx(100 * 2 * least_s() / 14e-3)


def test_the_forward_and_readers_of_a_result_are_left_out():
    bwd = [op("%flash_attention_bwd_dkv.3 = (f32[8,3,2048,64]", 4e6),
           op("%flash_attention_bwd_dq.3 = f32[8,9,2048,64]", 2e6)]
    got = read(*bwd,
               op("%flash_attention_pallas.18 = (f32[8,9,2048,64]", 9e6),
               op("%fusion.7 = f32[8,2048,9,64] fusion(f32[8,9,2048,64] "
                  "%flash_attention_bwd_dq.3)", 7e6))
    assert got == pytest.approx(read(*bwd))
    assert got == pytest.approx(100 * least_s() / 6e-3)


@pytest.mark.parametrize("ops", [
    [],
    [op("%flash_attention_pallas.18 = (f32[8,9,2048,64]", 9e6),
     op("%fusion.460 = bf16[8,3,64,2048,1]", 2e6)],
])
def test_no_backward_kernel_reads_none(ops):
    assert read(*ops) is None


def test_no_trace_reads_none():
    ctx = SimpleNamespace(config=CONFIG, traffic=TRAFFIC,
                          peak=conftest.CPU_PEAK, trace=None, info={})
    assert spec.reader("flash_attn_bwd_roofline").read(ctx) is None
