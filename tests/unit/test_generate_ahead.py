"""``generate()`` drives the served step, one step in flight: the order the
serving core keeps (``engine.launch_ahead``, then ``collect_step``), on the
three toys of ``tests/unit/test_run_ahead.py`` (a dense stack, the hybrid, the
window stack), whose helpers this file borrows. A file of its own: a file is
one worker's under ``--dist loadfile``, and every engine compiles its own
programs."""

import pytest

from deepspeed_tpu.serving.driver import ServingDriver
from tests.unit.test_run_ahead import (  # noqa: F401 (``toy`` is a fixture)
    _all_free, _prompt, _serve, _stop_token, _sync_reference, _work, toy)

GENERATE_MIX = [(0, 5, 6), (0, 70, 9), (0, 100, 4), (0, 33, 12), (0, 12, 1)]


@pytest.mark.parametrize("sampling", [{}, {"greedy": False, "temperature": 0.9, "seed": 7}],
                         ids=["greedy", "sampled"])
def test_generate_equals_the_driven_core(toy, sampling):
    """``generate()`` is the served step and nothing else: the same prompts
    through ``ServingDriver`` (uid = a prompt's index, which with the position
    is all a sampling key holds) give the same tokens, greedy and sampled."""
    _name, make = toy
    work = _work(GENERATE_MIX)
    new = max(n for _, _, n in work)
    outs = make(**sampling).generate([p for _, p, _ in work], max_new_tokens=new)
    reqs = _serve(ServingDriver(make(**sampling)), [(0, p, new) for _, p, _ in work])
    for (_, prompt, _), out, req in zip(work, outs, reqs):
        assert list(out[: len(prompt)]) == list(prompt)
        assert [int(t) for t in out[len(prompt):]] == req.generated and len(req.generated) == new


def _spy(engine, monkeypatch):
    """The order of the engine's launches and collects, as ("launch" |
    "collect", the flight's number: launches count from 0), and the flights
    by number; the real methods are still called."""
    calls, flights = [], []
    launch, collect = engine.launch_step, engine.collect_step

    def launched():
        flights.append(launch())
        calls.append(("launch", len(flights) - 1))
        return flights[-1]

    def collected(flight):
        calls.append(("collect", next(n for n, f in enumerate(flights) if f is flight)))
        return collect(flight)

    monkeypatch.setattr(engine, "launch_step", launched)
    monkeypatch.setattr(engine, "collect_step", collected)
    return calls, flights


def test_generate_collects_a_step_with_its_successor_in_flight(toy, monkeypatch):
    """Every step but the last is collected AFTER the next one was launched,
    each once and in order; the steps launched ahead took their decode rows'
    tokens from the device (``token_src`` names a slot, not the host)."""
    _name, make = toy
    engine = make()
    calls, flights = _spy(engine, monkeypatch)
    work = _work(GENERATE_MIX)
    outs = engine.generate([p for _, p, _ in work], max_new_tokens=9)
    assert [len(out) - len(p) for (_, p, _), out in zip(work, outs)] == [9] * len(work)
    last = len(flights) - 1
    assert last > 10 and [n for what, n in calls if what == "collect"] == list(range(last + 1))
    # launch 0, launch 1, collect 0, launch 2, collect 1, ..., collect last
    assert calls == [("launch", 0)] + [
        c for n in range(last) for c in (("launch", n + 1), ("collect", n))] + [("collect", last)]
    assert [f.stats.ahead for f in flights] == [False] + [True] * last
    assert not engine.scheduler.has_work() and _all_free(engine)


def test_generate_drops_the_row_in_flight_behind_an_eos(toy, monkeypatch):
    """A row ends on a token only its collect shows. Its next row is in
    flight by then: computed, never appended, and its blocks, state slot and
    ring are free at once; the other rows' streams are the reference's."""
    _name, make = toy
    work = [(0, _prompt(0, 20), 12), (0, _prompt(1, 50), 12)]
    ref = make()  # both reference runs on one engine: its programs compile once
    at, stop = _stop_token(_sync_reference(ref, work[:1])[0])
    want = _sync_reference(ref, work, stops={0: stop, 1: stop})
    assert len(want[0]) == at + 1
    engine = make()
    calls, flights = _spy(engine, monkeypatch)
    outs = engine.generate([p for _, p, _ in work], max_new_tokens=12, eos_token_id=stop)
    for uid, ((_, prompt, _), out) in enumerate(zip(work, outs)):
        assert [int(t) for t in out[len(prompt):]] == want[uid]
    assert outs[0][-1] == stop and len(outs[0]) == 20 + at + 1
    # row 0's EOS came with the collect of its (at + 1)-th step; the step
    # launched just before that collect holds row 0 again, and is collected later
    ended = [n for n, f in enumerate(flights) if 0 in f.rows][at]
    assert 0 in flights[ended + 1].rows and 0 not in flights[ended + 2].rows
    assert calls.index(("launch", ended + 1)) < calls.index(("collect", ended))
    assert not engine.scheduler.has_work() and _all_free(engine)
