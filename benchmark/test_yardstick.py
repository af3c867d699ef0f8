"""The yardstick's own arithmetic: the copied generators against the
program's, the kernel's byte count against `SeqConfig`, the layer-metric
reductions, the peaks table, the paced schedule, which saturated windows
stand, where the trace ends."""

import json
import os
import threading
import time

import pytest

from benchmark import client, generators, kernel_cost, layers, peaks, run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = run.load_json(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_generators_are_the_programs(seed):
    from kme_tpu import workload

    mine = list(generators.open_stream(
        "zipf_symbol_stream", 3000, seed,
        {"num_symbols": 64, "num_accounts": 128, "zipf_a": 1.2}))
    assert mine == workload.zipf_symbol_stream(3000, 64, 128, seed=seed,
                                               zipf_a=1.2)
    for validate in (False, True):
        mine = list(generators.open_stream("harness_stream", 3000, seed,
                                           {"validate": validate}))
        assert mine == workload.harness_stream(3000, seed=seed,
                                               validate=validate)


def outside_java_device_domain(m) -> bool:
    from kme_tpu import opcodes as op

    return m.action in (op.BUY, op.SELL) and not (
        0 <= m.price < 126 and m.size > 0)


def test_java_harness_stream_stays_in_the_java_device_domain():
    """Seed 2147483736 draws one trade that `--compat java` does not
    keep on the device (message 14,330 of the whole stream, its 23
    messages of preamble included: SELL price 58 size -1). With
    `validate`, which the configuration asks for, that one message is
    clamped and every other is the stock stream's."""
    from kme_tpu import workload

    config = run.load_json(os.path.join(HERE, "configs",
                                        "java-harness.json"))
    spec = config["stream"]
    assert spec["params"]["validate"] is True and config["reduced"] == []
    seed, events = 2147483736, 20000
    mine = list(generators.open_stream(spec["generator"], events, seed,
                                       spec["params"]))
    stock = list(generators.open_stream(
        spec["generator"], events, seed,
        dict(spec["params"], validate=False)))
    assert mine == workload.harness_stream(events, seed=seed, validate=True)
    assert len(mine) == len(stock) == events + 23
    assert not any(outside_java_device_domain(m) for m in mine)
    assert [k for k, m in enumerate(stock)
            if outside_java_device_domain(m)] == [14330]
    assert [k for k, (a, b) in enumerate(zip(mine, stock))
            if a != b] == [14330]
    assert (stock[14330].price, stock[14330].size) == (58, -1)
    assert (mine[14330].price, mine[14330].size) == (58, 1)


def test_program_generator_by_name():
    got = list(generators.open_stream(
        "kme_tpu.workload:cancel_heavy_stream", 500, 3,
        {"num_symbols": 8, "num_accounts": 16}))
    assert len(got) >= 500
    with pytest.raises(ValueError):
        generators.open_stream("no_such_stream", 1, 0, {})


def test_kernel_bytes_follow_seqconfig():
    from kme_tpu.engine import seq as SQ

    for name in ("fixed-zipf-1k", "java-harness"):
        config = run.load_json(os.path.join(HERE, "configs", f"{name}.json"))
        compat = kernel_cost.serve_option(config, "--compat")
        cfg = SQ.SeqConfig(compat=compat)
        assert (cfg.batch, cfg.fill_cap) == (kernel_cost.KERNEL_BATCH,
                                             kernel_cost.FILL_CAP)
        assert SQ.out_rows(cfg) == kernel_cost.out_rows()
        assert kernel_cost.seq_call_bytes(config) == 4 * (
            kernel_cost.MSG_PLANES[compat] * cfg.batch
            + SQ.out_rows(cfg) * SQ.LN)


def test_peaks_unknown_kind_is_an_error():
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.of("cpu")


def hb(t, batches, records, plan, produce_sum, produce_n):
    return {"time": t, "metrics": {
        "counters": {"service_batches": batches,
                     "service_records": records},
        "gauges": {"plan_s": plan, "parse_ns_per_msg": 900},
        "latencies": {"lat_produce": {"sum_s": produce_sum,
                                      "count": produce_n}}}}


def test_layer_reductions():
    ctx = {"hb_a": hb(100.0, 10, 20000, 1.0, 50.0, 20000),
           "hb_b": hb(120.0, 20, 40000, 1.5, 4050.0, 40000),
           "client": {"first_output_s": 15.5},
           "trace": {"window_s": 10.0, "programs": {
               "jit_call_scan(1)": {"seconds": 0.05, "runs": 5}}},
           "config": {"serve": ["--compat", "fixed"]},
           "device_kind": "TPU v5 lite"}
    H = {"from": "heartbeat"}
    B = "counters.service_batches"
    assert layers.read({**H, "reduce": "seconds_per_delta", "key": B,
                        "scale": 1000}, ctx) == pytest.approx(2000.0)
    assert layers.read({**H, "reduce": "delta_per", "key": "gauges.plan_s",
                        "per": B, "scale": 1000}, ctx) == pytest.approx(50.0)
    assert layers.read({**H, "reduce": "hist_mean",
                        "key": "latencies.lat_produce"},
                       ctx) == pytest.approx(0.2)
    assert layers.read({**H, "reduce": "last",
                        "key": "gauges.parse_ns_per_msg"}, ctx) == 900
    assert layers.read({**H, "reduce": "share_of_window",
                        "key": "gauges.plan_s"}, ctx) == pytest.approx(0.025)
    assert layers.read({"reduce": "sum", "terms": [
        {**H, "reduce": "seconds_per_delta", "key": B, "sign": 1},
        {**H, "reduce": "hist_mean", "key": "latencies.lat_produce",
         "sign": -1}]}, ctx) == pytest.approx(1.8)
    assert layers.read({"from": "client", "key": "first_output_s"},
                       ctx) == 15.5
    # 0.005 device s per traced s, 1000 messages per s -> 5 us a message
    assert layers.read({"from": "trace", "program": "^jit_call_scan",
                        "reduce": "program_us_per_message"},
                       ctx) == pytest.approx(5.0)
    roof = layers.read({"from": "trace", "program": "^jit_call_scan",
                        "reduce": "program_roofline", "scale": 100,
                        "bytes": "benchmark.kernel_cost:seq_call_bytes"},
                       ctx)
    nbytes = kernel_cost.seq_call_bytes(ctx["config"])
    assert roof == pytest.approx(100 * 5 * nbytes / 819e9 / 0.05)
    # nothing to read -> None, and the metric is left out
    assert layers.read({**H, "reduce": "last", "key": "gauges.absent"},
                       ctx) is None
    assert layers.read({"from": "trace", "program": "^nothing",
                        "reduce": "program_us_per_message"}, ctx) is None
    assert layers.read({"from": "client", "key": "gen_late_p99_ms"},
                       ctx) is None


def test_every_listed_metric_has_its_file():
    for m in BENCH["per_layer"]:
        spec = run.load_json(os.path.join(HERE, "layer_metrics",
                                          f"{m['name']}.json"))
        assert spec.get("cells") == m.get("workloads")
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    for w in BENCH["workloads"]:
        traffic, config = run.load_cell(w["name"])
        assert traffic["config"] == w["config"] == config["name"]
        reports = {m["name"] for m in BENCH["end_to_end"]
                   if w["name"] in m.get("workloads", [w["name"]])}
        assert {m["name"] for m in layers.load_for(w["name"], reports)} \
            == {m["name"] for m in BENCH["per_layer"]
                if w["name"] in m.get("workloads", [w["name"]])
                and m["moves"] in reports}


def write_heartbeat(path, t):
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"time": t, "metrics": {}}, f)
    os.replace(tmp, path)


def test_opening_heartbeat_is_one_stamped_after_the_window_opened(tmp_path):
    path, t_open, stop = tmp_path / "health.json", 1000.0, threading.Event()
    write_heartbeat(path, t_open - 0.4)     # the newest file at the opening
    later = threading.Timer(0.3, write_heartbeat, (path, t_open + 0.6))
    later.start()
    t = time.monotonic()
    hb = run.OpeningHeartbeat(str(path), t_open, stop, wait_s=5.0).get()
    later.join()
    assert hb["time"] == t_open + 0.6 and time.monotonic() - t < 2.0
    # none stamped after the opening: the run fails, it never falls back
    # to the stale file; a torn file is no heartbeat either
    for text in (json.dumps({"time": t_open - 0.4}), '{"time": 10'):
        path.write_text(text)
        with pytest.raises(run.RunFailure, match="no heartbeat stamped"):
            run.OpeningHeartbeat(str(path), t_open, stop, wait_s=0.3).get()
    # a run that ended meanwhile stops the wait
    stop.set()
    t = time.monotonic()
    with pytest.raises(run.RunFailure):
        run.OpeningHeartbeat(str(path), t_open, stop, wait_s=5.0).get()
    assert time.monotonic() - t < 1.0


def test_paced_schedules_keep_the_mean_rate():
    even = client.due_offsets({"rate_per_s": 660}, 30)
    assert len(even) == 19800 and even[1] == pytest.approx(1 / 660)
    bursts = client.due_offsets(
        {"rate_per_s": 600, "spacing": {"kind": "bursts", "period_s": 5,
                                        "burst_s": 0.5,
                                        "burst_share": 0.5}}, 30)
    assert len(bursts) == 18000 and bursts == sorted(bursts)
    assert sum(1 for d in bursts if d % 5 < 0.5) == 9000


@pytest.mark.parametrize("cell", next(
    m for m in BENCH["end_to_end"] if m["name"] == "p99_ms")["workloads"])
def test_a_tail_cell_holds_many_stalls_and_its_stream_suffices(cell):
    """`p99_ms` lies inside the snapshot stalls' distribution only where
    a window holds many of them: with one (660/s, PR 24 to PR 36) the
    99th percentile sat on the edge of that stall, a coin. And the
    schedule may not outrun the configuration's stream (every event is
    at least one message)."""
    traffic, config = run.load_cell(cell)
    due = len(client.due_offsets(traffic, BENCH["run_seconds"]))
    every = int(kernel_cost.serve_option(config, "--checkpoint-every"))
    assert due / every >= 8, (due, every)
    events = (traffic.get("stream") or config["stream"])["events"]
    assert traffic["warmup_messages"] + due <= events


T_OPEN, FIRST, LEAD = 1000.0, 6144, 8192


def served(orders, span, late=0, late_at=None):
    """Fetch clocks of a saturated run: the warm-up done by T_OPEN, the
    window's first 1,024 orders in the fetch that opened it (not after
    T_OPEN, so not in the window), then `orders` in batches of 2,048
    evenly up to T_OPEN + span; `late` more at `late_at` (None: never
    fetched) -> (last_t, sent)."""
    batches = -(-orders // 2048)
    last_t = [T_OPEN - 1.0] * (FIRST - 1) + [T_OPEN] * 1025
    for b in range(batches):
        last_t += [T_OPEN + span * (b + 1) / batches] * min(
            2048, orders - 2048 * b)
    if late_at is not None:
        last_t += [T_OPEN + late_at] * late
    return last_t, FIRST + 1024 + orders + late


SATURATED = {"t_open": T_OPEN, "first": FIRST}
# case -> (arguments of `served`, the kind says `drained`, orders_per_s or
# None for a run that fails, the rule the `window:` line names)
WINDOWS = {
    # what PR 42's tree did in zipf1k-sat: the whole stream in 9.7 s
    "drained at 9.7 s on 297,952 orders: stands on its work":
        ((297952, 9.7), True, 297952 / 9.7, "drain"),
    "drained at 16.5 s: stands on its seconds, the same arithmetic":
        ((297952, 16.5), True, 297952 / 16.5, "seconds"),
    "drained at 5 s on exactly 200,000 orders: stands":
        ((200000, 5.0), True, 200000 / 5.0, "drain"),
    "drained at 5 s on 199,999 orders: too little work":
        ((199999, 5.0), True, None, None),
    "drained at 3 s on 50,000 orders: too little work":
        ((50000, 3.0), True, None, None),
    "a server that stalled 9 s in on 250,000 orders, backlog unserved":
        ((250000, 9.0, LEAD), False, None, None),
    "the stream all sent, the server stalled before its last orders":
        ((250000, 9.0, LEAD), True, None, None),
    "the stream all sent, its last orders fetched after the close":
        ((250000, 9.0, LEAD, 31.0), True, None, None),
    "a stream that outlasts the window: closed at the last completion":
        ((540672, 29.8, LEAD, 30.2), False, 540672 / 29.8, "seconds"),
    "all sent 0.5 s before the close, the tail after it: by its seconds":
        ((540672, 29.8, LEAD, 30.2), True, 540672 / 29.8, "seconds"),
    "nothing completed in the window":
        ((0, 1.0, LEAD), False, None, None),
}


@pytest.mark.parametrize("case", WINDOWS)
def test_a_saturated_window_stands_on_its_seconds_or_on_its_work(case,
                                                                 capsys):
    """`run.window_numbers`, no server and no chip: a window that the
    drain closed before half of `--seconds` stands if it held
    MIN_DRAINED_ORDERS; one that closed early without the drain (a
    stalled server) fails however much it held."""
    clocks, drained, rate, rule = WINDOWS[case]
    last_t, sent = served(*clocks)
    facts = dict(SATURATED, drained=drained)
    if rate is None:
        with pytest.raises(run.RunFailure,
                           match="orders completed in the window .* stands "
                                 "from 15 s on, or closed at the drain on "
                                 "200000 orders or more"):
            run.window_numbers(facts, last_t, sent, 30.0)
        return
    metrics, attempted, failed, _ = run.window_numbers(facts, last_t, sent,
                                                       30.0)
    assert metrics == {"orders_per_s": pytest.approx(rate, rel=1e-12)}
    assert attempted == sent - FIRST and failed == sent - len(last_t)
    assert {"drain": f"(closed at the drain: {clocks[0]} orders >= 200000)",
            "seconds": "(closed at the last completion inside it)"}[
        rule] in capsys.readouterr().out


def test_a_paced_window_is_judged_on_latency_whatever_drained():
    due = [j / 100.0 for j in range(3000)]
    last_t = [T_OPEN - 1.0] * FIRST + [T_OPEN + d + 0.05 for d in due]
    for drained in (False, True):
        facts = dict(SATURATED, due=due, late=[0.001] * len(due),
                     drained=drained)
        metrics, attempted, failed, numbers = run.window_numbers(
            facts, last_t, FIRST + len(due), 30.0)
        assert metrics == {"p50_ms": pytest.approx(50.0),
                           "p99_ms": pytest.approx(50.0)}
        assert (attempted, failed) == (3000, 0)
        assert numbers == {"gen_late_p99_ms": pytest.approx(1.0)}


def test_the_trace_ends_when_the_window_has_closed(tmp_path, monkeypatch):
    """A stream that runs out closes the measured window at the drain;
    the parent says so with a second flag file and the trace ends there,
    not `--trace-seconds` later: nothing after the close is in it."""
    import jax

    from benchmark import host

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", time.monotonic())))
    opened, closed = tmp_path / "window.open", tmp_path / "window.closed"
    opened.touch()
    for close_after, lasts in ((0.3, (0.3, 1.0)),      # closed at the drain
                               (None, (1.5, 2.5))):    # never: S seconds
        closed.unlink(missing_ok=True)
        timer = threading.Timer(close_after or 0.0, closed.touch
                                if close_after else lambda: None)
        out, t = {}, time.monotonic()
        timer.start()
        host.trace_when_flagged(str(opened), str(closed), "D", 1.5,
                                threading.Event(), out)
        timer.join()
        assert lasts[0] <= time.monotonic() - t < lasts[1]
        assert lasts[0] - 0.1 <= out["t_stop"] - out["t_start"] < lasts[1]
        assert [c[0] for c in calls[-2:]] == ["start", "stop"]
    # the server's end stops it too, as before
    stop, out = threading.Event(), {}
    timer = threading.Timer(0.2, stop.set)
    timer.start()
    host.trace_when_flagged(str(opened), str(closed), "D", 5.0, stop, out)
    timer.join()
    assert out["t_stop"] - out["t_start"] < 1.0


def test_percentile_is_nearest_rank():
    assert run.percentile(list(range(1, 101)), 99) == 99
    assert run.percentile(list(range(1, 101)), 50) == 50
    assert run.percentile([1.0], 99) == 1.0


def test_heartbeats_go_one_at_a_time_and_none_after_the_closing(monkeypatch):
    from kme_tpu.bridge.service import MatchService

    from benchmark import host

    written = []
    monkeypatch.setattr(
        MatchService, "_write_heartbeat",
        lambda self, path, seen, tick=0, closing=False:
        written.append((seen, closing)))
    assert host.serialise_heartbeats() is True
    write = MatchService._write_heartbeat
    write(None, "health.json", 1, 1)
    write(None, "health.json", 2, 2, closing=True)
    write(None, "health.json", 3, 3)     # the beater, woken too late
    assert written == [(1, False), (2, True)]
