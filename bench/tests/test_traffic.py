"""The traffic generator: deterministic per seed, and the same work for
every seed, in its own order."""
import pytest

from benchlib.common import BENCH, load_json
from benchlib.traffic import call_order

MIX = load_json(BENCH / "traffic" / "back-to-back.json")


@pytest.mark.parametrize("seed", [7, 2**33 + 1, 2**40 + 5])
def test_kernel_call_order_is_seeded(seed):
    k = MIX["input_sets"]
    assert call_order(MIX, seed, 12) == call_order(MIX, seed, 12)
    order = call_order(MIX, seed, 4 * k)
    # every input set equally often, whatever the seed
    assert sorted(order) == sorted(list(range(k)) * 4)
    assert order[:k] == order[k:2 * k]
