"""The plan pool: every seed's window plans the same problems, in an
order of its own, through the trainer's own planner."""
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.drivers import trainer as drv  # noqa: E402

TRAFFIC = {"plan_pool": 14, "plan_pool_seed": 2204, "min_rounds": 5}


class FakeTrainer:
    """Records the stream seed and round each plan is made for."""

    def __init__(self, seed):
        self.tcfg = SimpleNamespace(seed=seed)
        self.calls = []

    def _plan_round(self, v, rnd):
        self.calls.append((self.tcfg.seed, rnd))
        return [], [], 0.0


def _window(seed, first=3, passes=2):
    tr = FakeTrainer(seed)
    drv.pool_plans(tr, TRAFFIC, seed)
    n = passes * TRAFFIC["plan_pool"]
    for rnd in range(first, first + n):
        tr._plan_round(1, rnd)
    assert tr.tcfg.seed == seed          # the trainer's own seed is back
    return tr.calls


def test_every_seed_plans_the_same_problems():
    a, b = _window(3_000_000_019), _window(7)
    assert {s for s, _ in a + b} == {TRAFFIC["plan_pool_seed"]}
    assert Counter(a) == Counter(b)
    assert Counter(r for _, r in a) == Counter(
        {r: 2 for r in range(TRAFFIC["plan_pool"])})
    assert a != b                        # in another order


def test_window_is_whole_passes_untraced():
    assert drv.window_rounds(TRAFFIC, 40, 1.45, True) == 28
    assert drv.window_rounds(TRAFFIC, 40, 0.32, True) == 126
    assert drv.window_rounds(TRAFFIC, 10, 30.0, True) == 14
    assert drv.window_rounds(TRAFFIC, 10, 1.45, False) == 7
    assert drv.window_rounds({"min_rounds": 5}, 40, 1.45, True) == 28
