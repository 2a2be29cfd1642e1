"""Plain reference of the MASK simulator under churn: tenants arrive and
leave at segment boundaries.

Written from the definition that the configuration's `membership` block
states, not from the program: it imports nothing of `repro`. One cycle is
`perfbench/reference.py`'s, with one change: each application slot runs
under its live ASID (`asid[slot]`, one generation per tenancy) instead of
`asid = slot`. Between two segments, every slot whose tenant differs from
the previous segment's is torn down:

  1. every entry of the L1 TLBs, the shared L2 TLB and the bypass cache
     whose ASID belongs to a changed slot (`asid % n_apps`) is invalidated;
  2. the page-walk cache, which holds no ASIDs, is flushed whole;
  3. the changed slots' in-flight walks are cancelled;
  4. their tokens restart from the initial state; the shared first-epoch
     latch is left alone;
  5. their DRAM-pressure inputs (concurrent walks, stalled warps) read 0
     until the next epoch's census;
  6. their warps rewind cold: stream position 0, no retired instructions,
     ready at once;
  7. their counters restart at 0;
  8. each gets the ASID `old + n_apps`, never used before.

The L2 data cache, the DRAM queues and open rows, the bypass rates and the
shared L2$ counters belong to no address space and stay. Segment 0 starts
from the cold state with no teardown, and a boundary at which no slot
changes does nothing, so a constant schedule is `reference.simulate`.

Statistics follow the program's definition (`reference.stats`): a changed
slot's counters count since its arrival, while its IPC divides by all the
cycles elapsed since cycle 0, and the shared L2$ hit rates
(`l2c_tlb_hit_rate`, `l2c_data_hit_rate`) count from cycle 0 for every
slot, arrivals included.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref
from perfbench.reference import (I32, U32, _geometry, _mix, _pfn,
                                 _pte_lines, _static_index, _vpn, dram_round,
                                 line_round, tlb_fill, tlb_probe)

# the one definition of a membership change this reference implements;
# a configuration that states another is refused
MEMBERSHIP = {
    "shootdown": ["l1_tlb", "l2_tlb", "bypass_cache"],
    "pwc": "flush_all_on_any_change",
    "walks": "cancel_changed",
    "tokens": "restart_changed_keep_first_epoch_latch",
    "dram_pressure": "zero_changed_until_next_census",
    "warps": "rewind_changed_cold",
    "counters": "zero_changed",
    "asid_generation_stride": "n_apps",
}


def cycle(cfg, design, params, s):
    """`reference.cycle` with each slot's live ASID, `s["asid"][slot]`, in
    place of the slot; `params` is (n_apps, fields)."""
    C, wpc, na = cfg["n_cores"], cfg["warps_per_core"], cfg["n_apps"]
    app_of_core, cores, warps = _geometry(cfg)
    kind = design["translation"]
    ideal, use_l2, use_pwc = (kind == "ideal", kind == "shared_l2_tlb",
                              kind == "pwc")
    if kind not in ("ideal", "shared_l2_tlb", "pwc"):
        raise ValueError(f"translation kind {kind!r} is not modelled here")
    tokens_on = design["tokens"] and use_l2
    bypass_on, dram_on = design["bypass"], design["dram"] == "mask"
    static = design["partition"] == "static"
    fi = {n: i for i, n in enumerate(cfg["app_fields"])}
    t = s["t"] + 1
    core = jnp.arange(C)

    # 1. warp scheduling
    wait = jnp.where(s["stall"] <= t, t - s["stall"], -1).reshape(C, wpc)
    pick = jnp.argmax(wait, 1)
    active = wait[core, pick] >= 0
    warp = core * wpc + pick
    app = jnp.asarray(app_of_core)
    p = params[app]
    pos = s["pos"][warp]
    vpn = _vpn(cfg, p, app, warp, pos, t)
    asid = s["asid"][app]

    # 2. translation
    l1 = s["l1"]
    m = (l1["tag"] == vpn[:, None]) & (l1["asid"] == asid[:, None])
    l1_hit = m.any(1) & active
    touch = l1_hit[:, None] & (jnp.arange(m.shape[1])[None] == jnp.argmax(
        m, 1)[:, None])
    l1 = dict(l1, lru=jnp.where(touch, t, l1["lru"]))
    if ideal:
        l1_hit = active
    l1_miss = active & ~l1_hit
    zc = jnp.zeros((C,), bool)
    l2tlb, byp, walk = s["l2tlb"], s["byp"], s["walk"]
    l2_hit = byp_hit = need_walk = merged = new_walk = zc
    tok = s["tok"]
    if not ideal:
        if use_l2:
            l2tlb, l2_hit = tlb_probe(l2tlb, vpn, asid, l1_miss, t)
        if tokens_on:
            byp, byp_hit = tlb_probe(byp, vpn, asid, l1_miss & ~l2_hit, t)
        need_walk = l1_miss & ~(l2_hit | byp_hit)
        if use_l2:
            per_core = tok["n"][app] // jnp.asarray(cores)[app]
            gate = ((pick < per_core) | tok["first"]) if tokens_on else True
            if tokens_on:
                byp = tlb_fill(byp, vpn, asid, need_walk & ~gate, t)
            l2tlb = tlb_fill(l2tlb, vpn, asid, need_walk & gate, t)
        victim = jnp.argmin(l1["lru"], 1)
        put = l1_miss[:, None] & (jnp.arange(m.shape[1])[None]
                                  == victim[:, None])
        l1 = dict(tag=jnp.where(put, vpn[:, None], l1["tag"]),
                  asid=jnp.where(put, asid[:, None], l1["asid"]),
                  lru=jnp.where(put, t, l1["lru"]))

        live = walk["done"] > t
        wm = ((walk["vpn"][None] == vpn[:, None])
              & (walk["asid"][None] == asid[:, None]) & live[None])
        merged = wm.any(1) & need_walk
        merge_done = jnp.where(merged, jnp.where(wm, walk["done"][None],
                                                 0).max(1), 0)
        joined = jnp.argmax(wm, 1)
        new_walk = need_walk & ~merged
        wt = cfg["max_concurrent_walks"]
        queue_pen = jnp.maximum(live.sum() + jnp.cumsum(new_walk) - wt, 0) \
            * cfg["walk_queue_penalty"]
        L = cfg["walk_levels"]
        pte = _pte_lines(L, asid, vpn)                       # (L, C)
        walk_on = jnp.tile(new_walk, L)
        if use_pwc:
            pwc, pwc_hit = line_round(s["pwc"], pte.reshape(-1), walk_on,
                                      jnp.ones((L * C,), bool), t, L)
        else:
            pwc, pwc_hit = s["pwc"], jnp.zeros((L * C,), bool)
        walk_go = walk_on & ~pwc_hit
        pwc_lat = cfg["pwc_hit_latency"] * (walk_on & pwc_hit).reshape(
            L, C).sum(0, dtype=I32)
    else:
        L, pwc = 0, s["pwc"]
        pte = jnp.zeros((0, C), I32)
        walk_go = jnp.zeros((0,), bool)

    # 3. data lines of the access
    pfn = _pfn(asid, vpn)
    r = _mix(pfn.astype(U32) + pos.astype(U32))
    l1d_hit = (r % U32(1024)).astype(I32) < p[:, fi["l1d_hit_milli"]]
    go_data = active & ~l1d_hit
    K = cfg["data_width"]
    data_lines = jnp.stack([
        pfn * 32 + (_mix(r + U32((0x85EBCA6B + 0x9E3779B9 * k) & 0xFFFFFFFF))
                    % U32(32)).astype(I32) for k in range(K)])

    # 4. shared L2 data cache + DRAM, walk levels then data lines
    W = L + K
    line = jnp.concatenate([pte.reshape(-1), data_lines.reshape(-1)])
    go = jnp.concatenate([walk_go, jnp.tile(go_data, K)])
    lane_app = jnp.tile(app, W)
    depth = jnp.concatenate([jnp.repeat(jnp.arange(1, L + 1, dtype=I32), C),
                             jnp.zeros((K * C,), I32)]).clip(0, 7)
    bp = s["bp"]
    if bypass_on:
        ok = ((bp["rate"] >= bp["rate"][0]) | ~bp["have"]
              | (bp["epoch"] % cfg["bypass_sample_every"] == 0))
        may_fill = ok.at[0].set(True)[depth]
    else:
        may_fill = jnp.ones((W * C,), bool)
    n_sets, n_ch = cfg["l2_sets"], cfg["n_channels"]
    if static:
        key = _static_index(line, n_sets, na, lane_app)
        channel = _static_index(line, n_ch, na, lane_app)
    else:
        key, channel = line % n_sets, line % n_ch
    l2c, hit = line_round(s["l2c"], line * n_sets + key, go,
                          may_fill, t, W)
    miss = go & ~hit
    dram, dlat = dram_round(
        cfg, s["dram"], channel, (line // n_ch) % cfg["n_banks"],
        line // (n_ch * cfg["n_banks"] * 32), lane_app,
        jnp.arange(W * C) < L * C, miss, dram_on, cfg["thres_max"], W)
    lat = jnp.where(hit, cfg["lat_l2_cache"], 0) + jnp.where(
        miss, cfg["lat_l2_cache"] + dlat, 0)
    oh = jax.nn.one_hot(depth, 8, dtype=I32) * go[:, None]
    bp = dict(bp, hits=bp["hits"] + (oh * hit[:, None]).sum(0),
              acc=bp["acc"] + oh.sum(0))
    lat_d = lat[L * C:].reshape(K, C)
    data_lat = jnp.where(l1d_hit, cfg["lat_l1_data"],
                         cfg["lat_l1_data"] + lat_d.max(0))
    data_hit = hit[L * C:].reshape(K, C).any(0)

    # 5. walk completion and translation latency
    if ideal:
        trans_lat = jnp.where(active, cfg["lat_l1_tlb"], 0)
        walk_time = dram_walk_lat = jnp.zeros((C,), I32)
        dram_walk_n = jnp.zeros((C,), I32)
        walk_hits = walk_probes = jnp.zeros((), I32)
    else:
        lat_w = lat[:L * C].reshape(L, C)
        went = walk_go.reshape(L, C) & ~hit[:L * C].reshape(L, C)
        done_new = t + cfg["lat_l2_tlb"] + lat_w.sum(0) + pwc_lat + queue_pen
        walk_time = done_new - t
        dram_walk_lat = jnp.where(went, lat_w, 0).sum(0)
        dram_walk_n = went.sum(0, dtype=I32)
        walk_hits = (hit[:L * C] & walk_go).sum(dtype=I32)
        walk_probes = walk_go.sum(dtype=I32)
        free_slots = jnp.sort(jnp.where(walk["done"] <= t, jnp.arange(wt),
                                        1 << 30))
        nth = jnp.cumsum(new_walk) - 1
        slot = jnp.where(new_walk, free_slots[jnp.clip(nth, 0, wt - 1)],
                         1 << 30)
        slot = jnp.where(new_walk & (slot < wt), slot, wt)
        walk = dict(vpn=walk["vpn"].at[slot].set(vpn, mode="drop"),
                    asid=walk["asid"].at[slot].set(asid, mode="drop"),
                    done=walk["done"].at[slot].set(done_new, mode="drop"),
                    merged=walk["merged"].at[slot].set(1, mode="drop"))
        walk["merged"] = walk["merged"].at[joined].add(merged.astype(I32))
        trans_lat = jnp.where(
            l1_hit, cfg["lat_l1_tlb"],
            jnp.where(l2_hit | byp_hit, cfg["lat_l2_tlb"],
                      jnp.where(merged, jnp.maximum(merge_done - t, 1),
                                jnp.maximum(done_new - t, 1))))

    # 6. retire
    gap = p[:, fi["gap"]]
    acc = s["instr"].dtype
    stall = s["stall"].at[warp].set(
        jnp.where(active, t + trans_lat + data_lat + gap, s["stall"][warp]))
    instr = s["instr"].at[warp].add(
        jnp.where(active, (1 + gap).astype(acc), jnp.zeros((), acc)))
    new_pos = s["pos"].at[warp].add(active.astype(I32))

    # 7. counters
    per_app = jax.nn.one_hot(app, na, dtype=I32) * active[:, None]
    eff = l2_hit | byp_hit
    tok = dict(tok, hits=tok["hits"] + ((eff & l1_miss)[:, None]
                                        * per_app).sum(0),
               misses=tok["misses"] + ((~eff & l1_miss)[:, None]
                                       * per_app).sum(0))
    cnt = jnp.stack([l1_hit, l1_miss, l2_hit, need_walk, byp_hit,
                     l1_miss & ~l2_hit, new_walk], 1).astype(I32)
    cnt = jnp.concatenate([cnt, dram_walk_n[:, None],
                           go_data[:, None].astype(I32)], 1)
    ints = s["ints"] + per_app.T @ cnt
    lat_sums = jnp.stack([jnp.where(new_walk, walk_time, 0),
                          merged.astype(I32), dram_walk_lat,
                          jnp.where(go_data, lat_d.max(0), 0)], 1)
    floats = s["floats"] + (per_app.T @ lat_sums).astype(acc)
    shared = s["shared"] + jnp.stack([
        walk_hits, walk_probes, (go_data & data_hit).sum(dtype=I32),
        go_data.sum(dtype=I32)])

    # 8. epoch: tokens hill-climb, DRAM pressure census, bypass rates
    adaptive = design["tokens"] or bypass_on or dram_on
    if adaptive:
        def epoch(args):
            tok, dram, bp = args
            wlive = (s["walk"]["done"] > t).astype(I32)
            slot_app = jax.nn.one_hot(s["walk"]["asid"] % na, na, dtype=I32)
            dram = dict(dram, conc=wlive @ slot_app,
                        stalled=(s["walk"]["merged"] * wlive) @ slot_app)
            total = jnp.maximum(tok["hits"] + tok["misses"], 1)
            rate = tok["misses"] / total
            keep = rate <= tok["prev"] - 0.01
            d = jnp.where(keep, tok["dir"], -tok["dir"])
            step = jnp.maximum((tok["n"] * jnp.float32(
                cfg["token_step_frac"])).astype(I32), 1)
            want = tok["n"] + d * step
            n = jnp.clip(want, 1, jnp.asarray(warps))
            d = jnp.where(want != n, -d, d)
            tok = dict(n=jnp.where(tok["first"], tok["n"], n),
                       dir=jnp.where(tok["first"], tok["dir"], d),
                       prev=rate, hits=jnp.zeros_like(tok["hits"]),
                       misses=jnp.zeros_like(tok["misses"]),
                       first=jnp.array(False))
            measured = bp["acc"] > cfg["bypass_min_accesses"]
            bp = dict(hits=jnp.zeros_like(bp["hits"]),
                      acc=jnp.zeros_like(bp["acc"]),
                      rate=jnp.where(measured, bp["hits"] * 1024
                                     // jnp.maximum(bp["acc"], 1), bp["rate"]),
                      have=bp["have"] | measured[0], epoch=bp["epoch"] + 1)
            return tok, dram, bp

        tok, dram, bp = jax.lax.cond(t % cfg["epoch_cycles"] == 0, epoch,
                                     lambda a: a, (tok, dram, bp))

    return dict(t=t, stall=stall, pos=new_pos, instr=instr, l1=l1,
                l2tlb=l2tlb, byp=byp, pwc=pwc, l2c=l2c, walk=walk, dram=dram,
                bp=bp, tok=tok, ints=ints, floats=floats, shared=shared,
                asid=s["asid"])


def teardown(cfg, s, change):
    """The state after a boundary at which the slots in `change` ((n_apps,)
    bool, some True) get new tenants."""
    na = cfg["n_apps"]
    app_of_core, _, _ = _geometry(cfg)
    fresh = ref.init_state(cfg, None, s["instr"].dtype)

    def shoot(c):
        dead = (c["asid"] >= 0) & change[c["asid"] % na]
        return dict(c, tag=jnp.where(dead, -1, c["tag"]),
                    asid=jnp.where(dead, -1, c["asid"]))

    s = dict(s, **{k: shoot(s[k]) for k in ("l1", "l2tlb", "byp")})
    s["pwc"] = dict(s["pwc"], tag=jnp.full_like(s["pwc"]["tag"], -1))
    w = s["walk"]
    gone = (w["asid"] >= 0) & change[w["asid"] % na]
    s["walk"] = {k: jnp.where(gone, fresh["walk"][k], v)
                 for k, v in w.items()}
    tok = {k: jnp.where(change, fresh["tok"][k], v)
           for k, v in s["tok"].items() if k != "first"}
    s["tok"] = dict(s["tok"], **tok)
    s["dram"] = dict(s["dram"], conc=jnp.where(change, 0, s["dram"]["conc"]),
                     stalled=jnp.where(change, 0, s["dram"]["stalled"]))
    warp_changed = jnp.repeat(change[jnp.asarray(app_of_core)],
                              cfg["warps_per_core"])
    s["stall"] = jnp.where(warp_changed, s["t"], s["stall"])
    s["pos"] = jnp.where(warp_changed, 0, s["pos"])
    s["instr"] = jnp.where(warp_changed, jnp.zeros_like(s["instr"]),
                           s["instr"])
    s["ints"] = jnp.where(change[:, None], 0, s["ints"])
    s["floats"] = jnp.where(change[:, None], jnp.zeros_like(s["floats"]),
                            s["floats"])
    s["asid"] = jnp.where(change, s["asid"] + na, s["asid"])
    return s


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, design_key, seg_cycles):
    """(one segment's scan, the teardown), jitted once per segment shape."""
    cfg, design = json.loads(cfg_key), json.loads(design_key)

    def segment(params, s):
        def body(s, _):
            return cycle(cfg, design, params, s), None
        return jax.lax.scan(body, s, None, length=seg_cycles)[0]

    return jax.jit(segment), jax.jit(functools.partial(teardown, cfg))


def simulate_trace(cfg, design_name, schedule, seg_cycles,
                   acc_dtype="float32"):
    """Counters at the end of each segment of `schedule` (one tuple of
    bench names, None for an idle slot, per segment of `seg_cycles`
    cycles): a list of dicts shaped as `reference.simulate`'s for one row,
    which `reference.stats(cfg, final, 0)` reads."""
    if {k: cfg["membership"].get(k) for k in MEMBERSHIP} != MEMBERSHIP:
        raise ValueError("the configuration states a membership change "
                         "this reference does not implement")
    keep = {k: v for k, v in cfg.items()
            if k not in ("apps", "app_category", "assumed", "guarantees",
                         "designs", "deployment", "source", "name")}
    run, tear = _programs(json.dumps(keep, sort_keys=True),
                          json.dumps(cfg["designs"][design_name],
                                     sort_keys=True), int(seg_cycles))
    out = []
    with jax.default_matmul_precision("highest"):
        s = dict(ref.init_state(cfg, None, jnp.dtype(acc_dtype)),
                 asid=jnp.arange(cfg["n_apps"], dtype=I32))
        prev = None
        for benches in schedule:
            if prev is not None:
                change = np.array([a != b for a, b in zip(prev, benches)])
                if change.any():
                    s = tear(s, jnp.asarray(change))
            prev = benches
            s = run(jnp.asarray(ref.app_rows(cfg, benches)), s)
            final = jax.device_get({k: s[k] for k in (
                "t", "instr", "ints", "floats", "shared")}
                | {"tokens": s["tok"]["n"]})
            out.append({k: np.asarray(v)[None] for k, v in final.items()})
    return out
