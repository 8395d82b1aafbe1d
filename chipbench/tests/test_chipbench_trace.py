"""The trace reduction: busy union, idle share, device time by op and
the idle gaps by host span, on a small hand-made trace whose answers
are counted by hand."""
from chipbench import trace
from chipbench.trace import Op, Span

# HLO text as the v5e's trace names device ops
DOT = ("%dot.2 = f32[512,256]{1,0:T(8,128)} dot(f32[512,1345]{1,0:T(8,128)} "
       "%p.1, f32[1345,256]{1,0:T(8,128)} %p.2), lhs_contracting_dims={1}")
SPLIT = ("%fusion.3 = (f32[512,19]{1,0:T(8,128)}, f32[]{:T(128)}) "
         "fusion(f32[512,19]{1,0:T(8,128)} %x.1), kind=kLoop")
COPY = ("%copy = f32[114,13]{1,0:T(8,128)} copy(f32[114,13]{0,1:T(8,128)} "
        "%args_0_.1)")


def _hand_trace():
    # window [1000, 2000) ns; ops overlap and spill over its edges
    ops = {"/device:TPU:0": [
        Op(COPY, 900, 200),                 # 1000..1100 inside
        Op("fusion.2", 1050, 100),          # 1050..1150, overlaps
        Op(DOT, 1300, 100),                 # 1300..1400
        Op(SPLIT, 1500, 50),                # 1500..1550
        Op(COPY, 1900, 300),                # 1900..2000 inside
        Op("late", 2500, 10)]}              # outside
    spans = [Span(trace.WINDOW, 1000, 2000),
             Span("master.on_batch_master", 1100, 1600),
             Span("member0.member_stage_send", 1150, 1250),
             Span("np.asarray(jax.Array)", 1420, 1480),
             Span("runtime", 1600, 1900)]   # not one of the benchmark's
    return ops, spans


def test_busy_union_and_idle_share():
    r = trace.reduce(*_hand_trace())
    # union: [1000,1150) + [1300,1400) + [1500,1550) + [1900,2000) = 400 ns
    assert abs(r["window_s"] - 1000e-9) < 1e-15
    assert abs(r["busy_s"] - 400e-9) < 1e-15
    assert abs(trace.idle_share({"trace": r}) - 60.0) < 1e-9


def test_device_ops_by_name_inside_the_window():
    r = trace.reduce(*_hand_trace())
    ops = dict(r["device_ops"])
    copy = "%copy copy f32[114,13]"
    assert abs(ops[copy] - 200e-9) < 1e-15          # 100 + 100 clipped
    assert abs(ops["%dot.2 dot f32[512,256]"] - 100e-9) < 1e-15
    assert abs(ops["%fusion.3 fusion (f32[512,19], f32[])"] - 50e-9) < 1e-15
    assert "late" not in ops


def test_idle_gaps_named_by_the_innermost_host_span():
    r = trace.reduce(*_hand_trace())
    gaps = dict(r["idle_gaps"])
    # [1150,1300) mid 1225 in member0's span; [1400,1500) mid 1450 in
    # the master's and a conversion inside it; [1550,1900) mid 1725 in
    # no span of the benchmark's, in another host span
    assert abs(gaps["member0.member_stage_send"] - 150e-9) < 1e-15
    assert abs(gaps["master.on_batch_master > np.asarray(jax.Array)"]
               - 100e-9) < 1e-15
    assert abs(gaps["host > runtime"] - 350e-9) < 1e-15


def test_no_trace_no_reading():
    assert trace.idle_share({"trace": None}) is None
