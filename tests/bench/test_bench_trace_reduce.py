"""Trace reduction on hand-built interval lists and on a trace recorded
here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr


def op(name, a, b, text=""):
    return (name, float(a), float(b), text)


def test_merge_overlaps_and_gaps():
    assert tr.merge([(0, 5), (3, 8), (10, 12), (12, 13), (20, 20)]) == \
        [(0, 8), (10, 13)]
    busy = tr.merge([(2, 4), (6, 9)])
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert tr.intersect([(0, 5), (7, 9)], [(3, 8)]) == [(3, 5), (7, 8)]


def test_busy_counts_nested_ops_once_and_clips_to_the_window():
    # a loop around two ops, one overlapping it: busy is the union
    t = tr.Trace(devices=[[op("while.1", 10, 50), op("fusion.2", 12, 20),
                           op("fusion.3", 45, 70)]], host=[])
    assert tr.busy(t, 0, 100) == 60
    assert tr.busy(t, 15, 55) == 40
    assert tr.busy(tr.Trace(devices=[], host=[]), 0, 1) is None


def test_four_device_planes_average():
    planes = [[op("fusion.1", 0, 10 * (i + 1))] for i in range(4)]
    t = tr.Trace(devices=planes, host=[])
    assert tr.busy(t, 0, 100) == pytest.approx((10 + 20 + 30 + 40) / 4)
    assert tr.op_time(t, lambda o: True, 0, 100) == pytest.approx(25)
    assert tr.op_time(t, lambda o: o[0] == "nope", 0, 100) is None


def test_exposed_collective_only_where_no_compute_runs():
    dev = [op("while.9", 0, 100), op("all-gather.1", 10, 30),
           op("fusion.2", 20, 25), op("all-reduce.4", 60, 70),
           op("fusion.5", 65, 80)]
    t = tr.Trace(devices=[dev, [op("fusion.1", 0, 50)]], host=[])
    # chip 0: [10,20)+[25,30)+[60,65) = 20 exposed; chip 1: none
    assert tr.exposed_collective(t, 0, 100) == pytest.approx(10)
    t1 = tr.Trace(devices=[[op("fusion.1", 0, 5)]], host=[])
    assert tr.exposed_collective(t1, 0, 10) is None


def test_top_ops_leave_out_loops_and_idle_gaps_take_the_innermost_span():
    dev = [op("while.1", 0, 100), op("fusion.1", 0, 40),
           op("fusion.1", 50, 60), op("custom-call.2", 70, 80)]
    host = [("bench.window", 0, 100), ("bench.step", 0, 100),
            ("decode_horizon", 0, 45), ("other", 62, 68)]
    t = tr.Trace(devices=[dev], host=host)
    assert tr.top_ops(t, 0, 100) == [["fusion.1", 50e-9],
                                     ["custom-call.2", 10e-9]]
    t = tr.Trace(devices=[dev[1:]], host=host)
    gaps = dict(tr.idle_gaps(t, 0, 100,
                             names=lambda n: n != "bench.window"))
    # gaps [40,50) and [80,100) fall in bench.step alone, [60,70) in other
    assert gaps == pytest.approx({"bench.step": 30e-9, "other": 10e-9})
    assert tr.window_of(t, "bench.window") == (0, 100)
    with pytest.raises(ValueError):
        tr.window_of(t, "missing")


def test_short_names_from_hlo_text():
    assert tr.short_name("%fusion.12 = bf16[2]{0} fusion(...)") == \
        "fusion.12"
    assert tr.short_name("while.3") == "while.3"


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    t = tr.load(str(tmp_path), chips=0)
    lo, hi = tr.window_of(t, "bench.window")
    assert hi > lo
    assert any(s[0] == "bench.step" and lo <= s[1] and s[2] <= hi
               for s in t.host)
    # the CPU backend has no device plane: nothing to call busy
    assert t.devices == [] and tr.busy(t, lo, hi) is None
    with pytest.raises(ValueError):
        tr.load(str(tmp_path), chips=1)


def plane(name, ops=(), line="XLA Ops", more=()):
    from types import SimpleNamespace as NS

    def events(evs):
        return [NS(name=n, start_ns=a, duration_ns=b - a) for n, a, b in evs]

    return NS(name=name, lines=[NS(name=line, events=events(ops))] + [
        NS(name=n, events=events(evs)) for n, evs in more])


def test_only_the_cells_chip_planes_are_reduced():
    # a TPU trace also holds device planes that are no chip; an empty one
    # averaged in would read a busy chip as half idle
    planes = [plane("#Chip0 Host Interface"),
              plane("/device:TPU:1", [("fusion.2", 0, 40)]),
              plane("/device:CUSTOM:Megascale Trace"),
              plane("/device:TPU:0", [("fusion.1", 0, 90),
                                      ("fusion.9", 90, 100)],
                    more=[("Steps", [("step.3", 0, 100)])]),
              plane("/host:CPU", [("bench.window", 0, 100)], "python")]
    one = tr.from_planes(planes, chips=1)
    assert [[o[0] for o in ops] for ops in one.devices] == \
        [["fusion.1", "fusion.9"]]
    assert tr.busy(one, 0, 100) == pytest.approx(100)
    assert tr.window_of(one, "bench.window") == (0, 100)
    two = tr.from_planes(planes, chips=2)
    assert tr.busy(two, 0, 100) == pytest.approx((100 + 40) / 2)
    with pytest.raises(ValueError):
        tr.from_planes(planes, chips=4)


def test_logprob_roofline_costs_only_whole_events_in_the_window():
    from types import SimpleNamespace

    from bench import flops, harness

    cfg = {"hidden_size": 4, "num_attention_heads": 1,
           "num_key_value_heads": 1, "num_hidden_layers": 2,
           "intermediate_size": 8, "vocab_size": 16}
    bwd_text = "%while.7 = (f32[4,16], f32[8,4]) while(...)"
    layer_loop = "%while.3 = (f32[2,4,8], f32[4,16]) while(...)"
    dev = [op("jvp_jit_logprob_stats_pallas__.1", 0, 10),
           op("while.7", 10, 30, bwd_text),
           op("logprob_stats_pallas.2", 12, 16),   # recompute, inside bwd
           op("while.3", 0, 100, layer_loop),      # the layer scan
           op("jvp_jit_logprob_stats_pallas__.1", 40, 50),
           op("while.7", 50, 120, bwd_text)]       # cut by the window
    ctx = SimpleNamespace(
        cfg=cfg, peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e12},
        data={"trace": tr.Trace(devices=[dev], host=[]), "lo": 0,
              "hi": 100, "logprob_rows": 8})
    share = harness.reader("logprob_roofline")(ctx)
    cost = flops.logprob_pass_cost(4, 16, 8)
    whole = {k: 2 * cost["fwd"][k] + cost["bwd"][k]
             for k in ("flops", "bytes")}
    assert share == pytest.approx(flops.roofline_share(
        whole, 40e-9, ctx.peaks)["share_pct"])
    ctx.data["trace"] = tr.Trace(devices=[[dev[3]]], host=[])
    assert harness.reader("logprob_roofline")(ctx) is None
