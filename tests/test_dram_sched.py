"""Address-Space-Aware DRAM scheduler unit tests (§5.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_sched as ds


def _state(n_apps=2):
    return ds.init(n_channels=2, n_banks=2, n_apps=n_apps)


def test_golden_beats_normal():
    st = _state()
    # two requests, same channel+bank+row, one TLB one data: golden first
    channel = jnp.asarray([0, 0])
    bank = jnp.asarray([0, 0])
    row = jnp.asarray([7, 7])
    app = jnp.asarray([0, 1])
    active = jnp.ones(2, bool)
    # order puts the data request FIRST — priority must still win
    is_tlb = jnp.asarray([False, True])
    _, lat = ds.access(st, channel, bank, row, app, is_tlb, active,
                       mask_enabled=True)
    assert int(lat[1]) < int(lat[0])


def test_frfcfs_row_hit_priority():
    st = _state()
    st = st._replace(open_row=st.open_row.at[0, 0].set(5))
    channel = jnp.asarray([0, 0])
    bank = jnp.asarray([0, 0])
    row = jnp.asarray([9, 5])          # second one hits the open row
    app = jnp.asarray([0, 0])
    is_tlb = jnp.zeros(2, bool)
    _, lat = ds.access(st, channel, bank, row, app, is_tlb,
                       jnp.ones(2, bool), mask_enabled=False)
    assert int(lat[1]) < int(lat[0])


def test_eq1_quota_proportional():
    st = _state()
    st = ds.update_pressure(st, jnp.asarray([30, 10]), jnp.asarray([20, 10]))
    q = np.asarray(ds.silver_quota(st, thres_max=500))
    # 30*20 : 10*10 = 6 : 1
    assert q[0] > 4 * q[1]
    assert q.sum() <= 510


def test_silver_rotation():
    st = _state()
    st = st._replace(silver_left=jnp.asarray(1, jnp.int32))
    channel = jnp.asarray([0])
    bank = jnp.asarray([0])
    row = jnp.asarray([1])
    app = jnp.asarray([0])              # app 0 is silver initially
    st2, _ = ds.access(st, channel, bank, row, app, jnp.asarray([False]),
                       jnp.asarray([True]), mask_enabled=True)
    assert int(st2.silver_app) == 1     # quota consumed -> rotate


def test_disabled_mask_is_single_queue():
    st = _state()
    cls = ds.classify(st, jnp.asarray([0, 1]), jnp.asarray([True, False]),
                      mask_enabled=False)
    assert tuple(np.asarray(cls)) == (2, 2)


def test_open_row_last_active_lane_wins():
    """Several requests to one (channel, bank) in one call: the last
    ACTIVE lane's row stays open (a dense max over the lanes that touch
    each (channel, bank) picks it, on every backend); a trailing inactive
    lane changes nothing."""
    st = _state()
    z = jnp.zeros(4, jnp.int32)
    st2, _ = ds.access(st, z, z, jnp.asarray([3, 7, 4, 9]), z,
                       jnp.zeros(4, bool),
                       jnp.asarray([True, True, False, False]),
                       mask_enabled=True)
    assert int(st2.open_row[0, 0]) == 7
    assert (np.asarray(st2.open_row).ravel()[1:] == -1).all()


def test_eq1_quota_matches_integer_reference():
    """Eq. (1) in exact integer arithmetic, including quotients that are
    exact integers (equal pressure splits thres_max evenly)."""
    rng = np.random.default_rng(0)
    cases = [([4, 4], [10, 10]), ([0, 0], [0, 0]), ([63, 1, 5], [960, 1, 7])]
    cases += [(rng.integers(0, 65, 3).tolist(), rng.integers(0, 961, 3)
               .tolist()) for _ in range(50)]
    for conc, stalled in cases:
        st = ds.update_pressure(ds.init(8, 8, len(conc)), jnp.asarray(conc),
                                jnp.asarray(stalled))
        w = [c * s for c, s in zip(conc, stalled)]
        want = [max(500 * x // max(sum(w), 1), 1) for x in w]
        assert np.asarray(ds.silver_quota(st, 500)).tolist() == want


def _access_loop(st, channel, bank, row, app, is_tlb, active, mask_enabled,
                 thres_max, waves):
    """`ds.access` as a plain per-lane Python loop in lane order: (open
    rows, queue lengths, silver app, silver quota left, latency)."""
    open_row = np.asarray(st.open_row).tolist()
    q = np.asarray(st.queue_len).tolist()
    s_app, s_left = int(st.silver_app), int(st.silver_left)
    w = [c * s for c, s in zip(np.asarray(st.conc_walks).tolist(),
                               np.asarray(st.warps_stalled).tolist())]
    quota = [max(thres_max * x // max(sum(w), 1), 1) for x in w]
    N = len(app)
    C = N // waves
    cls = [(0 if is_tlb[i] else 1 if app[i] == s_app else 2)
           if mask_enabled else 2 for i in range(N)]
    hit = []
    for i in range(N):
        h = open_row[channel[i]][bank[i]] == row[i]
        for v in range(i // C):           # the same position, earlier waves
            j = v * C + i % C
            h |= bool(active[j] and row[j] == row[i]
                      and (channel[j], bank[j]) == (channel[i], bank[i]))
        hit.append(h)
    key = [cls[i] * 2 + (not hit[i]) for i in range(N)]
    lat = [0] * N
    for k in range(waves):
        lanes = range(k * C, (k + 1) * C)
        for i in lanes:
            ahead = sum(1 for j in lanes if active[j]
                        and (channel[j], bank[j]) == (channel[i], bank[i])
                        and (key[j] < key[i] or (key[j] == key[i] and j < i)))
            service = ds.T_ROW_HIT if hit[i] else ds.T_ROW_MISS
            lat[i] = (service + (ahead + q[channel[i]][cls[i]])
                      * ds.T_QUEUE_UNIT) if active[i] else 0
        counts = [[0] * 3 for _ in q]
        for i in lanes:
            counts[channel[i]][cls[i]] += int(active[i])
        q = [[(q[c][x] * 3 + counts[c][x]) // 4 for x in range(3)]
             for c in range(len(q))]
        left = s_left - sum(1 for i in lanes if active[i] and cls[i] == 1)
        nxt = (s_app + 1) % len(quota)
        s_app, s_left = (nxt, quota[nxt]) if left <= 0 else (s_app, left)
    for i in range(N):
        if active[i]:
            open_row[channel[i]][bank[i]] = row[i]
    return open_row, q, s_app, s_left, lat


def _random_access(rng, waves, C, active_p):
    """A state and one call's lanes on 3 channels x 2 banks, so that most
    (channel, bank) pairs repeat within a wave and across waves."""
    st = ds.update_pressure(ds.init(3, 2, 3), rng.integers(0, 65, 3),
                            rng.integers(0, 961, 3))
    st = st._replace(open_row=jnp.asarray(rng.integers(-1, 3, (3, 2)),
                                          jnp.int32),
                     queue_len=jnp.asarray(rng.integers(0, 9, (3, 3)),
                                           jnp.int32),
                     silver_app=jnp.asarray(rng.integers(0, 3), jnp.int32),
                     silver_left=jnp.asarray(rng.integers(1, 6), jnp.int32))
    N = waves * C
    lanes = dict(channel=rng.integers(0, 3, N), bank=rng.integers(0, 2, N),
                 row=rng.integers(0, 3, N), app=rng.integers(0, 3, N),
                 is_tlb=rng.random(N) < 0.3, active=rng.random(N) < active_p)
    return st, {k: np.asarray(v, bool if k in ("is_tlb", "active")
                              else np.int32) for k, v in lanes.items()}


def _check_against_loop(st, lanes, mask_enabled, waves, got):
    st2, lat = got
    want = _access_loop(st, **{k: v.tolist() for k, v in lanes.items()},
                        mask_enabled=mask_enabled, thres_max=500,
                        waves=waves)
    assert np.asarray(st2.open_row).tolist() == want[0]
    assert np.asarray(st2.queue_len).tolist() == want[1]
    assert (int(st2.silver_app), int(st2.silver_left)) == want[2:4]
    assert np.asarray(lat).tolist() == want[4]


LOOP_CASES = ["waves1", "waves4", "waves8", "all_inactive",
              "trailing_inactive", "vmap3"]


@pytest.mark.parametrize("case", LOOP_CASES)
def test_access_matches_lane_order_loop(case):
    """`access` against a plain loop over lanes in lane order: in-batch
    ranking, per-wave backlog and queue counts, latency, silver rotation
    and the open-row table (the last active lane per (channel, bank)
    wins), with many duplicate (channel, bank) pairs."""
    rng = np.random.default_rng(LOOP_CASES.index(case))
    waves = {"waves1": 1, "waves4": 4, "all_inactive": 4}.get(case, 8)
    C = 24 if waves == 1 else 6
    fn = jax.jit(ds.access, static_argnames=("fr_fcfs", "waves"))
    if case == "vmap3":
        rows = [_random_access(rng, waves, C, 0.7) for _ in range(3)]
        st_b, lanes_b = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *rows)
        mask_b = jnp.asarray([True, False, True])
        got = jax.vmap(lambda s, ln, m: fn(s, **ln, mask_enabled=m,
                                           thres_max=500, waves=waves))(
            st_b, lanes_b, mask_b)
        for r, (st, lanes) in enumerate(rows):
            _check_against_loop(
                st, lanes, bool(mask_b[r]), waves,
                jax.tree_util.tree_map(lambda x: x[r], got))
        return
    st, lanes = _random_access(rng, waves, C,
                               0.0 if case == "all_inactive" else 0.7)
    if case == "trailing_inactive":
        # the last lane repeats an earlier active lane's (channel, bank)
        # with another row, inactive
        lanes["active"][-2:] = [True, False]
        for k in ("channel", "bank"):
            lanes[k][-1] = lanes[k][-2]
        lanes["row"][-1] = lanes["row"][-2] + 1
    _check_against_loop(st, lanes, True, waves,
                        fn(st, **{k: jnp.asarray(v) for k, v in lanes.items()},
                           mask_enabled=True, thres_max=500, waves=waves))


def test_access_lowers_without_scatter():
    """At the pair's shape (30 cores x 8 waves = 240 lanes, 8 x 8 banks)
    the DRAM round lowers with no scatter and no gather: XLA:TPU applies
    their indices one after another."""
    n = 240
    st = ds.init(8, 8, 2)
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    b = jax.ShapeDtypeStruct((n,), jnp.bool_)
    text = jax.jit(ds.access, static_argnames=("fr_fcfs", "waves")).lower(
        st, i32, i32, i32, i32, b, b, jax.ShapeDtypeStruct((), jnp.bool_),
        jax.ShapeDtypeStruct((), jnp.int32), waves=8).as_text()
    assert "stablehlo.reduce" in text       # the text is the program's
    assert "scatter" not in text
    assert "gather" not in text
