"""Property tests for the trace-time round schedules of the device-initiated
kernels — the three concrete builders of the ``CollectiveSchedule`` contract
in ``src/repro/core/schedule.py``: the moe_dispatch permutation-round
schedule (``DispatchSchedule``), the gemm_allgather broadcast-round schedule
(``BroadcastSchedule``), and the ring-rotation schedule (``RingSchedule``).

Invariants (docs/kernels.md — the schedule contract every kernel issues
its DMAs in):
  * every (edge, tile/microblock/chunk) event appears exactly once;
  * the round order is total, deterministic, and rank-independent (every
    rank walks the same round sequence);
  * the ``contexts``-deep send window never exceeds its cap and drains;
  * the sanitizers map any knob value to an exact divisor of the shape.
"""
import pytest

# property tests need hypothesis (optional test dep): skip, not error.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.schedule import (SendWindow, make_broadcast_schedule,
                                 make_ring_schedule, make_schedule,
                                 offset_key, respill_counts,
                                 sanitize_combine_tile, sanitize_kv_chunk,
                                 sanitize_tile_m, send_window_depths)

# ----------------------------------------------------- strategy definitions

bcast_scheds = st.builds(
    lambda n, nt, tile_m, fused: make_broadcast_schedule(
        n, nt * tile_m, tile_m, fused),
    n=st.integers(1, 8), nt=st.integers(1, 16),
    tile_m=st.sampled_from((8, 32, 128)), fused=st.booleans())

disp_scheds = st.builds(
    lambda counts, B, tight: make_schedule(counts, B, tight),
    counts=st.lists(st.integers(0, 300), min_size=1, max_size=8),
    B=st.sampled_from((16, 64)), tight=st.booleans())

ring_scheds = st.builds(
    lambda n, nc, kv_chunk, fused: make_ring_schedule(
        n, nc * kv_chunk, kv_chunk, fused),
    n=st.integers(1, 8), nc=st.integers(1, 16),
    kv_chunk=st.sampled_from((8, 32, 128)), fused=st.booleans())

contexts = st.sampled_from((1, 2, 4))


# ------------------------------------------------------- broadcast schedule

@given(bcast_scheds)
@settings(max_examples=200, deadline=None)
def test_broadcast_every_edge_exactly_once(s):
    rounds = s.rounds
    assert len(rounds) == len(set(rounds)) == s.issued_rounds()
    if s.fused:
        assert set(rounds) == {(off, t) for off in range(1, s.n)
                               for t in range(s.nt)}
    else:
        assert set(rounds) == {(off, 0) for off in range(1, s.n)}
    # dense: every round moves rows_per_round rows, totalling the wire
    assert len(rounds) * s.rows_per_round == s.wire_rows()


@given(bcast_scheds)
@settings(max_examples=200, deadline=None)
def test_broadcast_order_total_and_tile_major(s):
    """Lockstep order: the round list is rank-independent by construction
    (no rank appears in it) and strictly ordered tile-major — tile t's
    broadcast issues before any tile t+1 round, so the fused kernel can
    overlap tile t+1's GEMM with tile t's wire."""
    rounds = s.rounds
    assert rounds == sorted(rounds, key=lambda r: (r[1], r[0]))
    assert rounds == s.rounds            # deterministic (a pure property)


@given(bcast_scheds)
@settings(max_examples=200, deadline=None)
def test_broadcast_ticks_cover_wire(s):
    # COUNTER ticks split the per-edge wait into per-tile waits: the tick
    # count times the tile rows covers exactly the inbound wire
    ticks = s.completion_ticks(counter=True)
    if s.fused:
        assert ticks * s.tile_m == (s.n - 1) * s.M_l
    assert s.completion_ticks(counter=False) == s.n - 1


@given(st.one_of(bcast_scheds, disp_scheds, ring_scheds), contexts)
@settings(max_examples=200, deadline=None)
def test_send_window_never_exceeds_contexts(s, ctx):
    from repro.core.schedule import RingSchedule

    depths = s.send_window_depths(ctx)
    assert len(depths) == len(s.rounds)
    assert all(1 <= d <= max(1, ctx) for d in depths)
    # the window saturates once enough rounds exist (no artificial stall).
    # Ring kernels drain at every step boundary (the slot-credit
    # handshake), so their depth resets per step and saturates within one
    # step's rounds rather than across the whole list.
    if isinstance(s, RingSchedule):
        per_step = s.nc if s.fused else 1
        if s.steps:
            assert max(depths) == min(max(1, ctx), per_step)
    elif len(depths) >= ctx:
        assert max(depths, default=0) == min(ctx, len(depths))


# ----------------------------------------------------- dispatch (moe) rounds

@given(disp_scheds)
@settings(max_examples=200, deadline=None)
def test_dispatch_every_edge_exactly_once(s):
    rounds = s.rounds
    assert len(rounds) == len(set(rounds)) == s.n * s.b_max
    assert set(rounds) == {(off, j) for off in range(s.n)
                           for j in range(s.b_max)}


@given(disp_scheds)
@settings(max_examples=200, deadline=None)
def test_dispatch_wire_accounting_consistent(s):
    for rank in range(s.n):
        executed = s.executed_wire_tokens(rank)
        dummy = s.dummy_wire_tokens(rank)
        # lockstep rounds ship executed + dummy = the padded per-edge total
        assert executed + dummy == (s.n - 1) * s.b_max * s.block_tokens
        # the exact l3 credit never exceeds the block-rounded execution
        assert s.wire_tokens(rank) <= executed or not s.tight
    assert s.issued_rounds(elide_dummy=True) <= s.issued_rounds()


@given(disp_scheds)
@settings(max_examples=200, deadline=None)
def test_dispatch_block_major_order(s):
    """The tile-fused path's order: every (off, j) exactly once,
    microblock-major, each round a shift permutation — rank r sends to
    (r - off) % n, so every rank receives exactly once — and a pure
    function of the schedule, the same on every rank."""
    rounds = s.block_major_rounds
    assert len(rounds) == len(set(rounds)) == s.n * s.b_max
    assert set(rounds) == set(s.rounds)
    assert rounds == sorted(rounds, key=lambda r: (r[1], r[0]))
    for off, _j in rounds:
        assert sorted((r - off) % s.n for r in range(s.n)) \
            == list(range(s.n))
    assert rounds == s.block_major_rounds
    # the per-source paths keep the offset-major order
    assert s.rounds == sorted(s.rounds)


def _window_events(rounds, cap, key):
    """Push ``rounds`` through a SendWindow with recording hooks and drain
    it: the start/retire event list and each round's in-flight depth."""
    events, depth = [], {}

    def start(rnd):
        k = None if key is None else key(rnd)
        depth[k] = depth.get(k, 0) + 1
        events.append(("start", rnd, depth[k]))

    def wait(rnd):
        k = None if key is None else key(rnd)
        depth[k] -= 1
        events.append(("wait", rnd))

    window = SendWindow(cap, start=start, wait=wait)
    for rnd in rounds:
        window.push(rnd, key=None if key is None else key(rnd))
    window.drain()
    return events, depth


@given(disp_scheds, contexts, st.booleans(), st.sampled_from((None, 8)))
@settings(max_examples=200, deadline=None)
def test_keyed_send_window_per_key_depth_and_drain(s, ctx, fused, ct):
    """One window per offset: each key's depth stays within 1..contexts,
    the executable SendWindow matches the keyed mirror after every push,
    and the drain retires every round exactly once."""
    for rounds in s.send_phases(fused, ct)[0]:
        depths = send_window_depths(rounds, ctx, offset_key)
        assert len(depths) == len(rounds)
        assert all(1 <= d <= ctx for d in depths)
        events, left = _window_events(rounds, ctx, offset_key)
        assert [e[2] for e in events if e[0] == "start"] == depths
        assert sorted(e[1] for e in events if e[0] == "wait") \
            == sorted(rounds)
        assert all(v == 0 for v in left.values())
        # a key's window saturates once the key has enough rounds
        per_key = {}
        for rnd in rounds:
            per_key[offset_key(rnd)] = per_key.get(offset_key(rnd), 0) + 1
        for k, cnt in per_key.items():
            kd = [d for r, d in zip(rounds, depths) if offset_key(r) == k]
            assert max(kd) == min(ctx, cnt)


def _serial_window_depths(rounds, contexts):
    """The one-window algorithm as every kernel ran it before windows had
    keys: retire the oldest round once ``contexts`` are in flight."""
    cap = max(1, int(contexts))
    depth, out = 0, []
    for _ in rounds:
        if depth >= cap:
            depth -= 1
        depth += 1
        out.append(depth)
    return out


@given(st.one_of(bcast_scheds, disp_scheds, ring_scheds), contexts)
@settings(max_examples=200, deadline=None)
def test_unkeyed_send_window_unchanged(s, ctx):
    """Without a key (gemm_allgather, ring_attention, kv_shuttle and the
    per-source moe_dispatch paths) the mirror and the executable window
    are one window over all rounds: the same depths and the same
    retire-oldest order as before."""
    rounds = list(s.rounds)
    assert send_window_depths(rounds, ctx) \
        == _serial_window_depths(rounds, ctx)
    events, _ = _window_events(rounds, ctx, None)
    order = [e[1] for e in events]
    cap = max(1, ctx)
    expect = []
    for i, rnd in enumerate(rounds):
        if i >= cap:
            expect.append(rounds[i - cap])
        expect.append(rnd)
    expect += rounds[max(0, len(rounds) - cap):]
    assert order == expect
    if hasattr(s, "send_phases"):
        phases, key = s.send_phases(False)
        assert key is None and phases == (s.rounds, s.rounds)
        # one window over offset-major rounds never overlaps two peers
        # at contexts 1
        assert s.overlap_share(1, tile_fused=False) == 0.0


# ------------------------------------------------------ ring rotation rounds

@given(ring_scheds)
@settings(max_examples=200, deadline=None)
def test_ring_every_step_chunk_exactly_once(s):
    """Every (step, chunk) rotation event appears exactly once: n-1 shift
    steps, each split into nc chunks (fused) or one whole-shard round."""
    rounds = s.rounds
    assert len(rounds) == len(set(rounds)) == s.issued_rounds()
    if s.fused:
        assert set(rounds) == {(step, c) for step in range(s.steps)
                               for c in range(s.nc)}
    else:
        assert set(rounds) == {(step, 0) for step in range(s.steps)}
    # dense ring: every round moves rows_per_round rows of each rotated
    # tensor, totalling the (n-1)-shard wire
    assert len(rounds) * s.rows_per_round == s.wire_rows()


@given(ring_scheds)
@settings(max_examples=200, deadline=None)
def test_ring_order_total_and_step_major(s):
    """Lockstep order: rank-independent by construction and strictly
    step-major, chunk-ordered within a step — chunk c's send issues before
    chunk c+1's compute, and no step s+1 round precedes a step s round
    (the rotation's data dependence)."""
    rounds = s.rounds
    assert rounds == sorted(rounds)
    assert rounds == s.rounds            # deterministic (a pure property)


@given(ring_scheds)
@settings(max_examples=200, deadline=None)
def test_ring_ticks_cover_rotation(s):
    """The chunk-rotating kernels wait per-chunk semaphores whether ticks
    are interleaved (COUNTER) or drained up front (SIGNAL) — identical
    executed wait counts, so the model charges both the same; the tick
    count times the chunk rows covers exactly the rotated rows."""
    ticks = s.completion_ticks(counter=True)
    assert ticks == s.completion_ticks(counter=False)
    if s.fused:
        assert ticks * s.kv_chunk == s.steps * s.rows
    else:
        assert ticks == s.steps
    # a step has exactly nc chunk rounds (the drain boundary of the window)
    if s.fused and s.steps:
        step_rounds = [r for r in s.rounds if r[0] == 0]
        assert len(step_rounds) == s.nc


# ------------------------------------------- degraded-mode (fault) schedules

def draw_live(data, n):
    """A non-empty membership subset of an n-rank schedule."""
    return tuple(sorted(data.draw(
        st.sets(st.sampled_from(range(n)), min_size=1), label="live_ranks")))


@given(disp_scheds, contexts, st.data())
@settings(max_examples=200, deadline=None)
def test_dispatch_degrade_respills_and_keeps_contract(s, ctx, data):
    """degrade(live) respills the dead experts' tokens (conserving the
    total) into a smaller DispatchSchedule that re-satisfies the whole
    lockstep contract — live edges exactly once, total order, window cap."""
    live = draw_live(data, s.n)
    d = s.degrade(live)
    if len(live) == s.n:
        assert d is s
        return
    assert type(d) is type(s) and d.n == len(live)
    assert sum(d.counts) == sum(s.counts)          # token conservation
    assert all(c >= 0 for c in d.counts)
    assert (d.block_tokens, d.tight) == (s.block_tokens, s.tight)
    rounds = d.rounds
    assert len(rounds) == len(set(rounds)) == d.n * d.b_max
    assert set(rounds) == {(off, j) for off in range(d.n)
                           for j in range(d.b_max)}
    assert rounds == sorted(rounds)                # lockstep total order
    assert all(1 <= w <= max(1, ctx) for w in d.send_window_depths(ctx))


@given(bcast_scheds, contexts, st.data())
@settings(max_examples=200, deadline=None)
def test_broadcast_degrade_splices_and_keeps_contract(s, ctx, data):
    """degrade(live) splices dead ranks out of the shift permutation:
    same slab and tiling, offsets over the compacted live order only."""
    live = draw_live(data, s.n)
    d = s.degrade(live)
    if len(live) == s.n:
        assert d is s
        return
    assert type(d) is type(s) and d.n == len(live)
    assert (d.M_l, d.tile_m, d.fused) == (s.M_l, s.tile_m, s.fused)
    rounds = d.rounds
    offs = {(off, t) for off in range(1, d.n)
            for t in (range(d.nt) if d.fused else (0,))}
    assert len(rounds) == len(set(rounds)) and set(rounds) == offs
    assert rounds == sorted(rounds, key=lambda r: (r[1], r[0]))  # tile-major
    assert d.wire_rows() == (d.n - 1) * d.M_l      # no dead-rank edges
    assert all(1 <= w <= max(1, ctx) for w in d.send_window_depths(ctx))


@given(ring_scheds, contexts, st.data())
@settings(max_examples=200, deadline=None)
def test_ring_degrade_splices_and_keeps_contract(s, ctx, data):
    """degrade(live) closes the ring over the live order: same shard and
    chunking, len(live)-1 rotation steps, per-step window drain intact."""
    live = draw_live(data, s.n)
    d = s.degrade(live)
    if len(live) == s.n:
        assert d is s
        return
    assert type(d) is type(s) and d.n == len(live)
    assert (d.rows, d.kv_chunk, d.fused) == (s.rows, s.kv_chunk, s.fused)
    assert d.steps == len(live) - 1
    rounds = d.rounds
    assert len(rounds) == len(set(rounds)) and rounds == sorted(rounds)
    assert all(1 <= w <= max(1, ctx) for w in d.send_window_depths(ctx))


@given(st.lists(st.integers(0, 300), min_size=1, max_size=8), st.data())
@settings(max_examples=200, deadline=None)
def test_respill_conserves_tokens(counts, data):
    live = draw_live(data, len(counts))
    new = respill_counts(counts, live)
    assert len(new) == len(live)
    assert sum(new) == sum(counts)
    assert all(c >= counts[e] for c, e in zip(new, live))  # survivors keep own


# --------------------------------------------------------------- sanitizers

@given(st.integers(1, 256), st.integers(0, 512))
@settings(max_examples=200, deadline=None)
def test_sanitizers_return_divisors(B, req):
    ct = sanitize_combine_tile(req, B)
    assert B % ct == 0 and 1 <= ct <= B
    tm = sanitize_tile_m(req, B)
    assert B % tm == 0 and 1 <= tm <= B
    kc = sanitize_kv_chunk(req, B)
    assert B % kc == 0 and 1 <= kc <= B
    # one algorithm for the whole package (core/schedule.py::sanitize_tile)
    assert ct == tm == kc
