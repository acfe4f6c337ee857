"""The operation and byte counts against sums worked by hand from the
configuration's sizes."""
import pytest

from benchlib.common import BENCH, load_json
from benchlib.counts import Dispatch, skew_counts

EP4 = load_json(BENCH / "configs" / "granite-moe-ep4.json")
PEAKS = load_json(BENCH / "peaks.json")["chips"]["TPU v5 lite"]


def cell_dispatch():
    counts = skew_counts(EP4["rows_per_rank"], EP4["ranks"], EP4["skew"])
    return Dispatch(n=4, d=1536, f=512, counts=tuple(counts))


def test_one_expert_is_4_72_mflop_per_token():
    # gate and up: 2 * 1536 * 512 each, down: 2 * 512 * 1536
    d = Dispatch(n=4, d=1536, f=512, counts=(1, 0, 0, 0))
    assert d.flops_per_row == 3 * 2 * 1536 * 512 == 4_718_592


@pytest.mark.parametrize("rows,skew,want", [
    (1024, 3.0, [693, 230, 76, 25]),      # the cell's 3:1 law
    (128, 3.0, [88, 28, 9, 3]),           # the test size
    (1024, 1.0, [256, 256, 256, 256]),    # no skew
])
def test_skew_law_by_hand(rows, skew, want):
    assert skew_counts(rows, 4, skew) == want
    assert sum(want) == rows


def test_dispatch_cell_counts_by_hand():
    d = cell_dispatch()
    assert list(d.counts) == [693, 230, 76, 25]
    # every rank's 1024 rows through one expert each: 4 * 1024 rows
    assert d.flops() == 4 * 1024 * 4_718_592
    assert d.rank_flops(0) == 4 * 693 * 4_718_592       # 13.1 GFLOP
    # read 1024 rows and w1, w2 of one expert, write 1024 rows, f32
    assert d.rank_hbm_bytes(0) == 4 * (2 * 1024 * 1536 + 3 * 1536 * 512)
    # rank 0 takes 3 * 693 rows in and sends them back, and ships its own
    # 331 rows for experts 1-3 and gets them back, in f32
    assert d.rank_ici_bytes(0) == 2 * 1536 * 4 * (3 * 693 + 331)
    assert d.rank_ici_bytes(3) == 2 * 1536 * 4 * (3 * 25 + 999)


def test_least_time_is_ici_bound_on_rank_0():
    d = cell_dispatch()
    t, bound = d.least_time(PEAKS)
    assert bound == "ici"
    assert t == pytest.approx(d.rank_ici_bytes(0) / 200e9)
    assert 140e-6 < t < 160e-6
    # the FLOPs alone would take 66 us at the bf16 peak
    assert d.rank_flops(0) / 197e12 == pytest.approx(66.4e-6, rel=1e-2)
