"""The traffic generator: deterministic per seed, the same arrival times
and the same arm counts for every seed, and only the order changing."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import traffic

BENCH = Path(__file__).resolve().parents[1]
# an open-loop mix: the generator's arithmetic, whichever cells use it
STEADY = {"rate_per_s": 1.92, "ramp_s": 6, "schedule_seed": 20261016,
          "arms": {"sd35L+M@s=5": 1, "sd35L+M@s=10": 1, "sd35L+M@s=15": 1,
                   "sd35L+M@s=20": 1, "sd35L+M@s=25": 1}}
BACKLOG = json.loads((BENCH / "traffic" / "backlog-s15.json").read_text())


def _key(reqs):
    return [(r.due, r.arm, r.prompt_seed) for r in reqs]


@pytest.mark.parametrize("spec", [STEADY, BACKLOG], ids=["steady", "backlog"])
def test_same_seed_same_requests(spec):
    big = 2 ** 31 + 12345
    assert _key(traffic.requests(spec, big, 50)) == _key(
        traffic.requests(spec, big, 50))


def test_seeds_share_arrivals_and_arm_counts():
    a = traffic.requests(STEADY, 1, 50)
    b = traffic.requests(STEADY, 2 ** 33 + 1, 50)
    assert [r.due for r in a] == [r.due for r in b]
    assert Counter(r.arm for r in a) == Counter(r.arm for r in b)
    assert [r.arm for r in a] != [r.arm for r in b]
    assert [r.prompt_seed for r in a] != [r.prompt_seed for r in b]


def test_open_loop_rate_and_ramp():
    reqs = traffic.requests(STEADY, 3, 50)
    due = np.asarray([r.due for r in reqs])
    assert due[0] == pytest.approx(-STEADY["ramp_s"])
    assert np.all(np.diff(due) > 0)
    in_window = np.sum((due >= 0) & (due < 50))
    assert abs(in_window - 50 * STEADY["rate_per_s"]) <= 5
    counts = Counter(r.arm for r in reqs)
    assert max(counts.values()) - min(counts.values()) <= 1
    assert all(0 <= r.prompt_seed < traffic.PROMPT_SEED_LIMIT for r in reqs)


def test_backlog_is_due_at_open():
    reqs = traffic.requests(BACKLOG, 9, 50)
    assert len(reqs) == BACKLOG["backlog"]
    assert {r.due for r in reqs} == {0.0}
    assert {r.arm for r in reqs} == set(BACKLOG["arms"])


def test_phases_cycle_on_and_off():
    """On/off bursts are data: no arrival falls in an off phase, and the
    count follows the mean rate."""
    burst = dict(STEADY, phases=[[6.0, 5.0], [0.0, 15.0]])
    del burst["rate_per_s"]
    due = np.asarray([r.due for r in traffic.requests(burst, 5, 50)])
    into_cycle = np.mod(due + burst["ramp_s"], 20.0)
    assert np.all(into_cycle < 5.0) and np.all(np.diff(due) > 0)
    # 56 s from the first arrival: two whole cycles, then a whole on phase
    assert burst["ramp_s"] == 6 and len(due) == 3 * 6.0 * 5.0


def test_one_phase_is_the_constant_rate():
    one = dict(STEADY, phases=[[STEADY["rate_per_s"], 7.0]])
    del one["rate_per_s"]
    np.testing.assert_allclose(traffic.arrival_times(one, 50),
                               traffic.arrival_times(STEADY, 50), atol=1e-9)
