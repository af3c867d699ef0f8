"""Adaptive overload control (ISSUE 10): the degradation state machine,
priority-aware admission with per-account fairness, the AIMD backoff
contract, the deterministic shed-policy replay the CI gate rides on,
and the broker/service integration (shed_observer -> annotated REJ rows,
backoff hints on the TCP wire, the binary max_lag path untouched)."""

import json

import pytest

from kme_tpu.bridge.broker import (CLS_ADMIN, CLS_DRAIN, CLS_ORDER,
                                   BrokerOverload, InProcessBroker,
                                   OverloadController, classify_produce,
                                   simulate_overload)
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT
from kme_tpu.wire import (REJ_OVERLOAD, OrderMsg, dumps_order,
                          parse_order, rej_record_json)
from kme_tpu.workload import STORM_PROFILES, storm_stream, storm_windows


def _order(aid=1, oid=100, action=2):
    return dumps_order(OrderMsg(action=action, aid=aid, oid=oid,
                                sid=0, price=50, size=1))


CANCEL = _order(action=4)
PAYOUT = dumps_order(OrderMsg(action=200, sid=0, price=1))
TRANSFER = dumps_order(OrderMsg(action=101, aid=1, size=10))
ORDER = _order()


# -- classification ----------------------------------------------------


def test_classify_produce_priority_classes():
    assert classify_produce(CANCEL)[0] == CLS_DRAIN
    assert classify_produce(PAYOUT)[0] == CLS_DRAIN
    assert classify_produce(TRANSFER)[0] == CLS_ADMIN
    assert classify_produce(ORDER)[0] == CLS_ORDER
    # malformed input never gets the drain-priority fast lane
    assert classify_produce("not json")[0] == CLS_ORDER
    assert classify_produce('{"action": null}')[0] == CLS_ORDER
    cls, oid, aid = classify_produce(_order(aid=7, oid=42))
    assert (cls, oid, aid) == (CLS_ORDER, 42, 7)


# -- state machine -----------------------------------------------------


def test_state_machine_hysteresis():
    c = OverloadController(high_lag=10)     # low=5, drain=20
    assert c.state == c.NORMAL
    c.admit(ORDER, 9)
    assert c.state == c.NORMAL
    c.admit(ORDER, 10)                      # high watermark
    assert c.state == c.SHEDDING
    # stays shedding in the hysteresis band (low < backlog < high)
    c.admit(ORDER, 7)
    assert c.state == c.SHEDDING
    c.admit(ORDER, 5)                       # low watermark
    assert c.state == c.NORMAL
    # normal jumps straight to draining past drain_lag
    c.admit(ORDER, 20)
    assert c.state == c.DRAINING
    # draining exits ONLY through shedding, never direct to normal
    c.admit(ORDER, 0)
    assert c.state == c.SHEDDING
    c.admit(ORDER, 0)
    assert c.state == c.NORMAL
    assert c.transitions == 5


def test_latency_drives_shedding_below_backlog_threshold():
    c = OverloadController(high_lag=100, p99_budget_ms=10.0)
    for _ in range(50):
        c.observe_latency(0.100)            # 100 ms >> 10 ms budget
    assert c.lat_ewma_ms > 10.0
    ok, detail = c.admit(ORDER, 0)          # zero backlog, hot latency
    assert c.state == c.SHEDDING
    # ...and cool latency lets it recover
    for _ in range(100):
        c.observe_latency(0.0001)
    c.admit(ORDER, 0)
    assert c.state == c.NORMAL


def test_invalid_watermarks_rejected():
    with pytest.raises(ValueError):
        OverloadController(high_lag=1)
    with pytest.raises(ValueError):
        OverloadController(high_lag=10, low_lag=10)
    with pytest.raises(ValueError):
        OverloadController(high_lag=10, drain_lag=9)


# -- priority admission ------------------------------------------------


def test_draining_admits_only_book_shrinking_ops():
    c = OverloadController(high_lag=4, drain_lag=8)
    c.admit(ORDER, 8)                       # -> draining
    assert c.state == c.DRAINING
    assert c.admit(CANCEL, 8)[0] is True
    assert c.admit(PAYOUT, 8)[0] is True
    ok, detail = c.admit(TRANSFER, 8)
    assert ok is False and detail["state"] == "draining"
    ok, detail = c.admit(ORDER, 8)
    assert ok is False
    assert detail["threshold"] == 8 and detail["backlog"] == 8


def test_shedding_admits_drain_and_admin_rations_orders():
    c = OverloadController(high_lag=4, drain_lag=8)
    c.admit(ORDER, 5)                       # -> shedding
    assert c.state == c.SHEDDING
    assert c.admit(CANCEL, 5)[0] is True
    assert c.admit(TRANSFER, 5)[0] is True
    # the order ration shrinks as backlog approaches drain_lag: offer a
    # burst at high backlog and most must shed, but not all (linear
    # ramp, not a cliff)
    got = [c.admit(ORDER, 7)[0] for _ in range(20)]
    assert 0 < sum(got) < 20
    # at backlog >= drain_lag the ration hits zero
    assert not any(c.admit(ORDER, 8)[0] for _ in range(10))


def test_per_account_fairness_cap_blocks_flooder():
    c = OverloadController(high_lag=4, drain_lag=400, account_cap=0.5,
                           fair_window=16)
    c.admit(ORDER, 4)                       # -> shedding
    flooder_shed = other_admitted = 0
    for i in range(200):
        # flooder (aid=9) offers twice as often as the rotating others
        if i % 3 != 2:
            ok, detail = c.admit(_order(aid=9, oid=1000 + i), 4)
            if not ok and detail["fairness"]:
                flooder_shed += 1
        else:
            ok, _ = c.admit(_order(aid=i % 7, oid=2000 + i), 4)
            other_admitted += ok
    assert flooder_shed > 0
    assert other_admitted > 0
    assert c.fairness_sheds == flooder_shed


def test_aimd_backoff_grows_on_shed_halves_in_normal():
    c = OverloadController(high_lag=4, backoff_step_ms=5,
                           backoff_max_ms=20)
    c.admit(ORDER, 8)                       # draining -> shed
    for _ in range(10):
        c.admit(ORDER, 8)
    assert c.backoff_ms == 20               # additive growth, bounded
    # recovery: draining -> shedding -> normal, then halving decay
    c.admit(CANCEL, 0)
    c.admit(CANCEL, 0)
    assert c.state == c.NORMAL
    before = c.backoff_ms
    c.admit(ORDER, 0)
    assert c.backoff_ms == before // 2


# -- deterministic replay (the CI gate's substrate) --------------------


def test_simulate_overload_deterministic_and_sheds():
    lines = [dumps_order(m) for m in storm_stream(
        "flash-crowd", 1500, num_symbols=8, num_accounts=16, seed=0)]
    wins = storm_windows("flash-crowd", 1500, num_symbols=8,
                         num_accounts=16)
    a = simulate_overload(lines, wins, OverloadController(high_lag=32))
    b = simulate_overload(lines, wins, OverloadController(high_lag=32))
    assert a["admitted_idx"] == b["admitted_idx"]
    assert a["shed"] > 0
    assert a["admitted"] + a["shed"] == a["total"] == len(lines)


# profile -> (symbols, accounts, shed_frac): what the deterministic
# overload replay sheds of each storm at 4,000 events,
# seed 0, high_lag 32, drain 2.0 per message — a scale at which every
# profile's burst overwhelms the modeled drain. No wall clock and no
# RNG enters, so only a change to the admission policy, the priority
# classing or a profile's generator can move a figure.
STORM_SHED = {
    "payout-storm-wide": (64, 32, 0.015),
    "flash-crowd": (32, 32, 0.1938),
    "cancel-storm": (16, 32, 0.0659),
    "hot-book": (8, 32, 0.1461),
    "liquidation-cascade": (32, 32, 0.0959),
}


@pytest.mark.parametrize("profile", sorted(STORM_PROFILES))
def test_storm_profile_shed_frac(profile):
    from kme_tpu.oracle import OracleEngine

    symbols, accounts, frac = STORM_SHED[profile]
    lines = [dumps_order(m) for m in storm_stream(
        profile, 4000, num_symbols=symbols, num_accounts=accounts,
        seed=0)]
    wins = storm_windows(profile, 4000, num_symbols=symbols,
                         num_accounts=accounts)
    a, b = (simulate_overload(lines, wins,
                              OverloadController(high_lag=32),
                              drain_per_msg=2.0) for _ in range(2))
    assert a["admitted_idx"] == b["admitted_idx"]
    assert a["shed"] > 0
    assert round(a["shed_frac"], 4) == frac
    # shedding is a pure input filter: what survives must still be a
    # stream the engine can process
    eng = OracleEngine("fixed")
    for i in a["admitted_idx"]:
        eng.process(parse_order(lines[i]))


def test_simulate_cancels_shed_strictly_less_than_orders():
    # the acceptance criterion: under a cancel-storm / flash-crowd
    # style mix that sheds, class-0 (cancel/payout) shed rate is
    # STRICTLY below class-2 (new order) shed rate
    for name in ("cancel-storm", "flash-crowd"):
        lines = [dumps_order(m) for m in storm_stream(
            name, 2000, num_symbols=8, num_accounts=16, seed=0)]
        wins = storm_windows(name, 2000, num_symbols=8,
                             num_accounts=16)
        ctl = OverloadController(high_lag=24)
        sim = simulate_overload(lines, wins, ctl)
        assert sim["shed"] > 0, name
        snap = sim["controller"]
        offered = {c: snap["admitted_by_class"][c]
                   + snap["shed_by_class"][c] for c in range(3)}
        assert offered[CLS_ORDER] > 0, name
        rate_order = (snap["shed_by_class"][CLS_ORDER]
                      / offered[CLS_ORDER])
        if offered[CLS_DRAIN]:
            rate_drain = (snap["shed_by_class"][CLS_DRAIN]
                          / offered[CLS_DRAIN])
            assert rate_drain < rate_order, name


# -- wire: annotated REJ rows ------------------------------------------


def test_rej_record_json_detail_is_additive():
    # without detail the bytes are unchanged from every prior release
    base = rej_record_json(5, 7, REJ_OVERLOAD)
    assert base == ('{"oid":5,"aid":7,"reason":9,'
                    '"rej":"rej_overload"}')
    assert rej_record_json(5, 7, REJ_OVERLOAD, detail=None) == base
    assert rej_record_json(5, 7, REJ_OVERLOAD, detail={}) == base
    got = rej_record_json(5, 7, REJ_OVERLOAD, detail={
        "threshold": 48, "backlog": 50, "state": "shedding",
        "backoff_ms": 15})
    doc = json.loads(got)
    assert doc["backlog"] == 50 and doc["state"] == "shedding"
    assert doc["rej"] == "rej_overload"
    # keys append in sorted order (stable bytes for parity tooling)
    assert got.index('"backlog"') < got.index('"backoff_ms"') \
        < got.index('"state"') < got.index('"threshold"')


# -- broker integration ------------------------------------------------


def _armed_broker(**kw):
    """Broker with the controller armed: the commit watermark must
    exist before backlog is measurable (same contract as max_lag)."""
    b = InProcessBroker(overload=OverloadController(**kw))
    provision(b)
    b.commit(TOPIC_IN, 0)
    return b


def test_broker_sheds_orders_admits_cancels_with_backoff_hint():
    b = _armed_broker(high_lag=2, drain_lag=4)
    admitted, first = 0, None
    for i in range(12):
        try:
            b.produce(TOPIC_IN, None, _order(aid=i % 5, oid=i))
            admitted += 1
        except BrokerOverload as e:
            if first is None:
                first = e
    assert first is not None and admitted > 0
    assert first.backoff_ms and first.backoff_ms > 0
    assert first.detail["state"] in ("shedding", "draining")
    assert first.detail["backlog"] >= 2
    assert first.detail["threshold"] in (2, 4)
    assert b.overload_rejects == 12 - admitted
    # ...while a cancel still gets through (book-shrinking fast lane),
    # even with the backlog pinned at its worst
    off = b.produce(TOPIC_IN, None, CANCEL)
    assert off == admitted
    # consuming drains the backlog and re-opens admission (two drain
    # ops walk draining -> shedding -> normal)
    b.commit(TOPIC_IN, admitted + 1)
    b.produce(TOPIC_IN, None, CANCEL)
    b.commit(TOPIC_IN, admitted + 2)
    b.produce(TOPIC_IN, None, _order(aid=99, oid=100))


def test_broker_shed_observer_fires_outside_lock():
    seen = []
    b = _armed_broker(high_lag=2, drain_lag=4)
    b.shed_observer = lambda topic, d: seen.append((topic, d))
    shed_oids = []
    for i in range(12):
        try:
            b.produce(TOPIC_IN, None, _order(aid=i % 5, oid=i))
        except BrokerOverload:
            shed_oids.append(i)
    assert shed_oids
    assert [d["oid"] for _, d in seen] == shed_oids
    assert all(t == TOPIC_IN for t, _ in seen)
    assert all(d["aid"] == d["oid"] % 5 for _, d in seen)
    # the observer must be able to call back INTO the broker (it runs
    # outside the data lock) — e.g. to annotate the shed on MatchOut
    b.shed_observer = lambda topic, d: b.produce(
        TOPIC_OUT, "REJ", rej_record_json(d["oid"], d["aid"],
                                          REJ_OVERLOAD, detail={
                                              "backlog": d["backlog"],
                                              "state": d["state"]}))
    got = None
    for i in range(20):
        try:
            b.produce(TOPIC_IN, None, _order(aid=i % 5, oid=100 + i))
        except BrokerOverload:
            got = 100 + i
            break
    assert got is not None
    rej = [r for r in b.fetch(TOPIC_OUT, 0, 100) if r.key == "REJ"]
    assert rej and json.loads(rej[-1].value)["oid"] == got


def test_binary_max_lag_path_unchanged_and_composable():
    # the historical binary shed must keep working without a controller
    b = InProcessBroker(max_lag=2)
    provision(b)
    b.commit(TOPIC_IN, 0)
    b.produce(TOPIC_IN, None, _order(oid=1))
    b.produce(TOPIC_IN, None, _order(oid=2))
    with pytest.raises(BrokerOverload) as ei:
        b.produce(TOPIC_IN, None, CANCEL)   # binary: sheds EVERYTHING
    assert ei.value.backoff_ms is None      # no AIMD hint on this path
    assert b.overload_rejects == 1
    # and it takes precedence when both are configured
    b2 = InProcessBroker(max_lag=2,
                         overload=OverloadController(high_lag=50))
    provision(b2)
    b2.commit(TOPIC_IN, 0)
    b2.produce(TOPIC_IN, None, _order(oid=1))
    b2.produce(TOPIC_IN, None, _order(oid=2))
    with pytest.raises(BrokerOverload):
        b2.produce(TOPIC_IN, None, CANCEL)


def test_unarmed_controller_broker_admits_everything():
    # no commit watermark -> no backlog signal -> no shedding (matches
    # the max_lag arming contract)
    b = InProcessBroker(overload=OverloadController(high_lag=2))
    provision(b)
    for i in range(50):
        b.produce(TOPIC_IN, None, _order(oid=i))
    assert b.overload_rejects == 0


# -- service integration -----------------------------------------------


def test_service_publishes_controller_gauges_and_annotates_sheds():
    from kme_tpu.bridge.service import MatchService

    b = InProcessBroker(overload=OverloadController(high_lag=4,
                                                    drain_lag=8))
    provision(b)
    svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                       annotate_rejects=True)
    assert b.shed_observer is not None      # annotation tap installed
    admitted = sheds = 0
    for i in range(60):
        try:
            b.produce(TOPIC_IN, None, _order(aid=i % 5, oid=i))
            admitted += 1
        except BrokerOverload:
            sheds += 1
    assert sheds > 0 and admitted > 0
    svc.run(max_messages=admitted)
    g = svc.telemetry.snapshot()["gauges"]
    assert g["overload_state"] is not None
    assert g["shed_by_class2"] == sheds
    assert g["admitted_by_class2"] == admitted
    assert "overload_backoff_ms" in g and "overload_transitions" in g
    # every shed produced an annotated REJ row on MatchOut
    rej = [r for r in b.fetch(TOPIC_OUT, 0, 4096) if r.key == "REJ"]
    over = [json.loads(r.value) for r in rej
            if json.loads(r.value)["reason"] == REJ_OVERLOAD]
    assert len(over) == sheds
    for doc in over:
        assert {"backlog", "threshold", "state",
                "backoff_ms"} <= set(doc)


# -- chaos scenario registry -------------------------------------------


def test_chaos_scenario_registry_lists_all_scenarios():
    from kme_tpu.bridge.chaos import scenario_registry
    from kme_tpu.workload import STORM_PROFILES

    reg = scenario_registry()
    assert {"default", "failover", "shard-failover"} <= set(reg)
    assert set(STORM_PROFILES) <= set(reg)
    assert all(isinstance(v, str) and v for v in reg.values())


def test_chaos_list_scenarios_flag(capsys):
    from kme_tpu.bridge import chaos

    assert chaos.main(["--list-scenarios"]) == 0
    out = capsys.readouterr().out
    for name in ("default", "failover", "payout-storm-wide",
                 "liquidation-cascade"):
        assert name in out
