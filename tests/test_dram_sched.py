"""Address-Space-Aware DRAM scheduler unit tests (§5.4)."""
import jax.numpy as jnp
import numpy as np

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
    ACTIVE lane's row stays open (the scatter applies duplicate updates in
    lane order, on the CPU and on a TPU v5e alike); a trailing inactive
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
