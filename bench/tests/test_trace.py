"""The reduction from a profiler trace to busy time, kernel time and
idle gaps matched to host spans."""

import collections
import os

import pytest

import trace_reduce as tr


def _ev(name, s, e, **stats):
    return tr.Event(name, float(s), float(e), stats)


@pytest.fixture
def synthetic():
    ops = [_ev("fusion.1", 100, 200), _ev("fusion.2", 150, 300),
           _ev("fp16_matmul_pallas.7", 500, 600, hlo="custom-call()"),
           _ev("fusion.3", 900, 1200)]
    spans = [_ev("bench.window", 0, 1000), _ev("bench.tick", 250, 700),
             _ev("bench.frontend", 320, 480), _ev("bench.admit", 700, 950)]
    modules = [_ev("jit_decode_block(3)", 100, 300),
               _ev("jit_prefill(5)", 500, 600)]
    return tr.Trace(ops, modules, spans, 1, 0.0, 1000.0)


def test_busy_is_the_union_of_ops(synthetic):
    # [100, 300) and [500, 600) and [900, 1000): 400 ns in the window
    assert tr.busy_ns(synthetic) == 400.0


def test_idle_gaps_longest_first(synthetic):
    assert tr.idle_gaps(synthetic) == [(600.0, 900.0), (300.0, 500.0),
                                       (0.0, 100.0)]


def test_gaps_matched_to_host_spans(synthetic):
    got = tr.top_gaps(synthetic)
    # 600-900: the admit span covers 200 ns of it, the tick 100 ns
    assert got[0] == ["admit", pytest.approx(300e-9)]
    # 300-500: the tick covers all of it, the frontend nested in it less
    assert got[1][0] == "tick"
    assert got[2][0] == "none"


def test_kernel_and_module_time(synthetic):
    k = [e for e in tr.clip(synthetic.ops, synthetic.lo, synthetic.hi)
         if tr.matches(e, ("fp16_matmul_pallas",))]
    assert [e.dur for e in k] == [100.0]
    assert tr.module_time(synthetic, ("decode_block",)) == \
        pytest.approx(200e-9)
    assert tr.top_ops(synthetic)[0] == ["fusion", pytest.approx(350e-9)]


def test_shapes_from_hlo_text():
    text = "%c = f32[128,384]{1,0} custom-call(bf16[128,512]{1,0} %x)"
    assert tr.shapes_in(text) == [("f32", (128, 384), True),
                                  ("bf16", (128, 512), True)]
    vmem = "%c = f32[8,512]{1,0:T(8,128)S(1)} custom-call()"
    assert tr.shapes_in(vmem) == [("f32", (8, 512), False)]


# ---------------------------------------------------- a trace from the chip
CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "chip_trace.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    """``record_trace.py`` on one TPU v5e: a frontend call, one
    admission, a 20 ms host pause, three decode ticks."""
    return tr.load(CHIP_TRACE)


def test_chip_trace_programs_and_spans(chip):
    progs = collections.Counter(m.name for m in
                                tr.clip(chip.modules, chip.lo, chip.hi))
    assert progs["jit_decode_block"] == 3
    assert progs["jit_prefill"] == 1
    names = collections.Counter(s.name for s in chip.spans)
    assert names["bench.tick"] == 3 and names["bench.admit"] == 1
    assert names["bench.frontend"] == 1 and names["bench.pause"] == 1
    assert chip.n_devices == 1 and chip.window_s > 0.02


def test_chip_trace_busy_and_gaps_add_up(chip):
    busy = tr.busy_ns(chip)
    gaps = tr.idle_gaps(chip)
    assert 0 < busy < chip.hi - chip.lo
    assert busy + sum(t - s for s, t in gaps) == \
        pytest.approx(chip.hi - chip.lo, rel=1e-9)
    # the host pause leaves the device idle for at least its 20 ms, and
    # the longest gap is matched to it
    top = tr.top_gaps(chip)
    assert top[0][0] == "pause" and top[0][1] >= 0.019
    assert {name for name, _ in top} <= {"pause", "tick", "admit",
                                         "frontend", "none"}


def test_chip_trace_kernels(chip):
    import spec
    peaks = spec.load_peaks("TPU v5 lite")
    for kernel in ("fp16_matmul", "flash_attention"):
        km = spec.kernel_model(kernel)
        evs = [e for e in tr.clip(chip.ops, chip.lo, chip.hi)
               if tr.matches(e, km.TRACE_NAMES)]
        assert evs, kernel
        for e in evs:
            flops, nbytes = km.cost(e)
            least = max(flops / peaks["bf16_flops"],
                        nbytes / peaks["hbm_bytes_per_s"])
            assert flops > 0 and nbytes >= 0
            # no call runs faster than the chip's roofline allows
            assert least <= e.dur * 1e-9 * 1.05, (kernel, e.name)
