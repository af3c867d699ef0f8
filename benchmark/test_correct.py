"""`correct` shown to fail. The benchmark's own runs do not run these.

1. The control of each configuration (the plain reference of another
   guarantee, see `control` in benchmark/configs/*.json) must not pass
   the byte comparison.
2. A whole run of the harness with the look for a chip waived and the
   timed path broken underneath (one MatchOut record altered where the
   serve loop produces it) must report `correct: false`; the same run
   on the sound host reports true, and under `--control` false.
3. The seed whose stock stream holds a trade outside the java device
   domain (2147483736, message 14,330) stays on the device session with
   the configuration's `validate` and reads `correct: true`; the same
   run on the stock stream leaves it and reads false: the check guards
   the program now, not the dice.
4. A stream drawn more slowly than the server's patience (it ends
   itself when its input stays silent) does not lose the run: the
   server is fed while the stream is drawn, and the window opens only
   after the last message exists."""

import itertools
import os
import time

import pytest

from benchmark import generators, judge, run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "java-harness-sat"   # small state: a test run can hold it


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
@pytest.mark.parametrize("name", ["fixed-zipf-1k", "java-harness"])
def test_control_reference_fails_the_comparison(name, seed):
    config = run.load_json(os.path.join(HERE, "configs", f"{name}.json"))
    s = config["stream"]
    msgs = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), 12000))
    want = judge.make_reference(config["reference"]).process_wire(msgs)
    ctrl = judge.make_reference(
        config["control"]["reference"]).process_wire(msgs)
    flat = lambda groups: [ln for g in groups for ln in g]  # noqa: E731
    assert judge.differing(flat(want), flat(want)) == 0
    assert judge.differing(flat(ctrl), flat(want)) > 0


def rehearse(tmp_path, seed=11, seconds=3, events=60000, **kw):
    return run.run_cell(CELL, seed=seed, seconds=seconds, trace=False,
                        allow_cpu=True, events=events,
                        out=str(tmp_path / "run"), **kw)


def test_sound_run_is_correct(tmp_path):
    result = rehearse(tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] == "cpu"
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


def test_control_run_is_not_correct(tmp_path):
    assert rehearse(tmp_path, control=True)["correct"] is False


LEAVING_SEED, LEAVES_AT = 2147483736, 14330


def rehearse_leaving_seed(tmp_path, capsys):
    """-> (result, what the run printed); long enough a window that the
    message at LEAVES_AT is served on a host half as fast (the
    interpreter reaches it 2.5 s into the window here), long enough a
    stream that it outlasts half the window on a program twice as fast:
    a rehearsal never holds the 200,000 orders on which a drained window
    stands, so it has to stand on its seconds, and from LEAVES_AT on the
    stock stream is served by the native engine at 37,000 messages a
    second. The length costs little: the stream is drawn while the
    server starts, and what the window does not reach is neither sent
    nor judged."""
    result = rehearse(tmp_path, seed=LEAVING_SEED, seconds=6, events=200000)
    traffic, _config = run.load_cell(CELL)
    assert result["attempted"] + traffic["warmup_messages"] > LEAVES_AT
    return result, capsys.readouterr().out


def test_the_stream_stays_on_the_device_session(tmp_path, capsys):
    result, said = rehearse_leaving_seed(tmp_path, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert "check ok   left the device session: 0 (limit 0)" in said


def test_the_stock_stream_leaves_the_device_session(tmp_path, capsys,
                                                    monkeypatch):
    traffic, config = run.load_cell(CELL)
    config["stream"]["params"]["validate"] = False
    monkeypatch.setattr(run, "load_cell", lambda cell: (traffic, config))
    result, said = rehearse_leaving_seed(tmp_path, capsys)
    assert result["correct"] is False
    assert "check MISS left the device session: 1 (limit 0)" in said
    assert "check MISS engine in effect: 'native' (limit 'seq')" in said
    assert said.count("check MISS") == 2    # byte-exact all the same


def test_broken_timed_path_is_not_correct(tmp_path):
    result = rehearse(tmp_path, host_module="benchmark.broken_host")
    assert result["correct"] is False


def test_without_the_switch_the_cpu_is_refused(tmp_path):
    with pytest.raises(run.RunFailure, match="not the chip"):
        run.run_cell(CELL, seed=11, seconds=3, trace=False, events=60000,
                     out=str(tmp_path / "run"))


def slow_harness_stream(num_events, seed=0, **params):
    """`harness_stream` at about 4,000 messages a second: faster than the
    interpreter serves, slower than the start-up hides."""
    for k, m in enumerate(generators.harness_stream(num_events, seed,
                                                    **params)):
        if k % 20 == 0:
            time.sleep(0.005)
        yield m


def test_a_slowly_drawn_stream_does_not_lose_the_run(tmp_path, monkeypatch):
    traffic, config = run.load_cell(CELL)
    traffic["stream"] = dict(
        config["stream"],
        generator="benchmark.test_correct:slow_harness_stream")
    monkeypatch.setattr(run, "load_cell", lambda cell: (traffic, config))
    monkeypatch.setattr(run, "IDLE_EXIT_S", 2.0)
    result = rehearse(tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    # the window opened after the stream's end, not at the warm-up's
    assert result["attempted"] < 60000 - traffic["warmup_messages"] - 10000
