"""Hypothesis property tests for the TLB and page-table cores.

Kept separate from test_core_tlb.py so the deterministic unit tests still
run when `hypothesis` is absent; this module skips itself gracefully.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from perfbench import reference  # noqa: E402
from repro.core import page_table as pt  # noqa: E402
from repro.core import tlb as tlb_mod  # noqa: E402


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=16),
       st.integers(0, 3))
def test_tlb_property_fill_probe(vpns, asid):
    st_ = tlb_mod.init(64, 16)
    v = jnp.asarray(vpns, jnp.int32)
    a = jnp.full((len(vpns),), asid, jnp.int32)
    act = jnp.ones(len(vpns), bool)
    st_ = tlb_mod.fill(st_, v, a, act, 1)
    # at least the LAST filled instance of each distinct set survives
    st_, hit = tlb_mod.probe(st_, v, a, act, 2)
    # every distinct vpn whose set wasn't contended must hit
    sets = [x % 4 for x in vpns]
    for i, x in enumerate(vpns):
        if sets.count(x % 4) == 1:
            assert bool(hit[i]), (vpns, i)


# ------------------------------------------------------- access_fused
# The fused-round contract, checked from an empty cache (tags -1, random
# LRU): every tag change is then a fill, which makes the
# port/victim/forwarding properties directly observable.

_SETS, _WAYS = 4, 2


def _fused_round(lru0, vpn, act, mf, n_waves):
    tags = jnp.full((_SETS, _WAYS), -1, jnp.int32)
    state = tlb_mod.TLBState(
        tags=tags, asids=jnp.full((_SETS, _WAYS), -1, jnp.int32),
        lru=jnp.asarray(lru0, jnp.int32).reshape(_SETS, _WAYS),
        hits=jnp.zeros((), jnp.int32), misses=jnp.zeros((), jnp.int32))
    state, hit, filled = tlb_mod.access_fused(
        state, jnp.asarray(vpn, jnp.int32), jnp.asarray(act),
        jnp.asarray(mf), 7, n_waves=n_waves)
    return (np.asarray(state.tags), np.asarray(state.lru),
            np.asarray(hit), np.asarray(filled))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_access_fused_contract_properties(data):
    W = data.draw(st.sampled_from([1, 2, 3]), label="n_waves")
    C = data.draw(st.sampled_from([1, 2, 4]), label="lanes_per_wave")
    N = W * C
    vpn = np.asarray(data.draw(st.lists(
        st.integers(0, 30), min_size=N, max_size=N)))
    act = np.asarray(data.draw(st.lists(
        st.booleans(), min_size=N, max_size=N)))
    mf = np.asarray(data.draw(st.lists(
        st.booleans(), min_size=N, max_size=N)))
    lru0 = np.asarray(data.draw(st.lists(
        st.integers(0, 50), min_size=_SETS * _WAYS,
        max_size=_SETS * _WAYS))).reshape(_SETS, _WAYS)
    tags1, lru1, hit, filled = _fused_round(lru0, vpn, act, mf, W)

    set_ix = vpn % _SETS
    wave = np.arange(N) // C

    # fill-port uniqueness: at most one fill per (set, wave)
    ports = list(zip(set_ix[filled].tolist(), wave[filled].tolist()))
    assert len(ports) == len(set(ports)), ports

    # victim-chain monotonicity: the r fills a set received landed in
    # exactly its r least-recently-used ways (stable (lru, way) order)
    for s in range(_SETS):
        changed = set(np.nonzero(tags1[s] != -1)[0].tolist())
        r = int((filled & (set_ix == s)).sum())
        lru_order = np.lexsort((np.arange(_WAYS), lru0[s]))
        assert changed == set(lru_order[:r].tolist()), (s, tags1, lru0)

    # forwarding == post-fill re-probe: from an empty cache there are no
    # pre-hits, so a lane hits iff it is active, did not fill itself,
    # and its line is present in the post-fill tags of its set
    expect_hit = act & ~filled & \
        (tags1[set_ix] == vpn[:, None]).any(1)
    np.testing.assert_array_equal(hit, expect_hit)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_access_fused_equals_reference_bitwise(data):
    """`access_fused` == the plain reference's `line_round`: tags, LRU
    and hits, bit for bit."""
    W = data.draw(st.sampled_from([1, 2, 3]))
    C = data.draw(st.sampled_from([1, 2, 4]))
    N = W * C
    vpn = data.draw(st.lists(st.integers(0, 30), min_size=N, max_size=N))
    act = data.draw(st.lists(st.booleans(), min_size=N, max_size=N))
    mf = data.draw(st.lists(st.booleans(), min_size=N, max_size=N))
    lru0 = np.asarray(data.draw(st.lists(
        st.integers(0, 50), min_size=_SETS * _WAYS,
        max_size=_SETS * _WAYS))).reshape(_SETS, _WAYS)
    tags, lru, hit, _ = _fused_round(lru0, vpn, act, mf, W)
    want, want_hit = reference.line_round(
        dict(tag=jnp.full((_SETS, _WAYS), -1, jnp.int32),
             lru=jnp.asarray(lru0, jnp.int32)),
        jnp.asarray(vpn, jnp.int32), jnp.asarray(act), jnp.asarray(mf), 7, W)
    for got, exp, name in ((tags, want["tag"], "tags"),
                           (lru, want["lru"], "lru"), (hit, want_hit, "hit")):
        np.testing.assert_array_equal(got, np.asarray(exp), err_msg=name)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**20 - 1), st.integers(0, 2**20 - 1),
       st.integers(0, 63))
def test_pte_root_sharing_property(vpn_a, vpn_b, asid):
    """Near-root PTE lines are shared by nearby VPNs; leaves diverge."""
    cfg = pt.PageTableConfig()
    la = np.asarray(pt.pte_line_addresses(cfg, jnp.int32(asid),
                                          jnp.int32(vpn_a)))
    lb = np.asarray(pt.pte_line_addresses(cfg, jnp.int32(asid),
                                          jnp.int32(vpn_b)))
    # level 0 covers 2^27+ pages per line -> always shared for 20-bit vpns
    assert la[0] == lb[0]
    if vpn_a // 16 == vpn_b // 16:
        assert la[-1] == lb[-1]   # same leaf line
