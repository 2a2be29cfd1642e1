"""Plain reference of the MASK memory-system simulator.

Written from the model's definition, not from the program: it imports
nothing of `repro` and reads every size, latency, design and application
stream from the configuration file. One simulated cycle, for every core:

  1. pick the ready warp that has waited longest; draw its page (VPN) from
     the application's stream parameters;
  2. probe the core's L1 TLB, then the shared L2 TLB and the token bypass
     cache; walks that miss merge with an in-flight walk of the same page
     or start one (four page-table levels, optionally through the PWC);
  3. the walk levels and the four divergent data lines of the access go to
     the shared L2 data cache and DRAM as eight ordered waves;
  4. the warp stalls for the translation + data + compute latency;
  5. counters accumulate per application; every `epoch_cycles` the tokens,
     the DRAM silver quota and the bypass rates adapt.

Duplicate writes to one cache way or one DRAM bank in a cycle resolve to
the latest lane in wave order, written out explicitly here.

`acc_dtype` is the precision of the retired-instruction and latency-sum
accumulators, and `stats(dtype=...)` that of the statistics derived from
them; the configuration states float32 and float64. The control runs the
same code one step lower, in bfloat16 and float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
I32 = jnp.int32


def _mix(x):
    """The address streams' 32-bit xorshift-multiply mixer."""
    x = x.astype(U32)
    x = x ^ (x >> 16)
    x = x * U32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * U32(0x846CA68B)
    return x ^ (x >> 16)


def _vpn(cfg, p, app, warp, pos, t):
    """Page touched by each core's picked warp (p: (C, fields) rows)."""
    f = {n: p[:, i] for i, n in enumerate(cfg["app_fields"])}
    page = pos // jnp.maximum(f["revisit"], 1)
    r = _mix(page.astype(U32) * U32(2654435761)
             + warp.astype(U32) * U32(40503) + app.astype(U32))
    sel = (r % U32(1024)).astype(I32)
    r2 = _mix(r + U32(0x9E3779B9))
    hot_span = U32(1) + _mix(r2) % f["hot_pages"].astype(U32)
    hot = (r2 % hot_span).astype(I32)
    warm = f["hot_pages"] + (r2 % f["warm_pages"].astype(U32)).astype(I32)
    base = f["hot_pages"] + f["warm_pages"]
    seq = base + ((t // 64) * f["stride"] + (warp // 8) % 4) % f["ws_pages"]
    rnd = base + (r2 % f["ws_pages"].astype(U32)).astype(I32)
    a, b, c = (f["hot_milli"], f["hot_milli"] + f["warm_milli"],
               f["hot_milli"] + f["warm_milli"] + f["seq_milli"])
    v = jnp.where(sel < a, hot, jnp.where(sel < b, warm,
                                          jnp.where(sel < c, seq, rnd)))
    return v + app * (1 << 22)


def _pfn(asid, vpn):
    return (_mix(asid.astype(U32) * U32(0x9E3779B9) + vpn.astype(U32))
            & U32(0x3FFFFFFF)).astype(I32)


def _pte_lines(levels, asid, vpn):
    """(levels, C) physical line of each level's page-table entry."""
    out = []
    for k in range(levels):
        prefix = vpn.astype(U32) >> U32((levels - 1 - k) * 9)
        region = asid.astype(U32) * U32(levels + 1) + U32(k + 1)
        out.append(((_mix(region) & U32(0x0FFFFFFF)) + prefix // U32(16))
                   .astype(I32))
    return jnp.stack(out)


def _set_of(key, n_sets):
    return key % n_sets if n_sets > 1 else jnp.zeros_like(key)


def _first_per(group, on, n_groups):
    """Bool per lane: the lowest lane among the `on` lanes of its group."""
    lane = jnp.arange(group.shape[0], dtype=I32)
    first = jnp.full((n_groups,), group.shape[0], I32).at[
        jnp.where(on, group, n_groups)].min(lane, mode="drop")
    return on & (first[jnp.clip(group, 0, n_groups - 1)] == lane)


def _last_per(group, on, n_groups):
    """Bool per lane: the highest lane among the `on` lanes of its group."""
    lane = jnp.arange(group.shape[0], dtype=I32)
    last = jnp.full((n_groups,), -1, I32).at[
        jnp.where(on, group, n_groups)].max(lane, mode="drop")
    return on & (last[jnp.clip(group, 0, n_groups - 1)] == lane)


# --------------------------------------------------------------- caches
def tlb_probe(c, key, asid, active, t):
    """Set-associative probe; a hit refreshes its way's last-use time."""
    n_sets, n_ways = c["tag"].shape
    s = _set_of(key, n_sets)
    match = (c["tag"][s] == key[:, None]) & (c["asid"][s] == asid[:, None])
    hit = match.any(1) & active
    way = jnp.argmax(match, 1)
    lru = c["lru"].at[jnp.where(hit, s, n_sets), way].set(t, mode="drop")
    return dict(c, lru=lru), hit


def tlb_fill(c, key, asid, on, t):
    """One fill per set (the lowest lane wins) into its least recently
    used way (lowest way on ties)."""
    n_sets, n_ways = c["tag"].shape
    s = _set_of(key, n_sets)
    win = _first_per(s, on, n_sets)
    victim = jnp.argmin(c["lru"][s], 1)
    ss = jnp.where(win, s, n_sets)
    return dict(tag=c["tag"].at[ss, victim].set(key, mode="drop"),
                asid=c["asid"].at[ss, victim].set(asid, mode="drop"),
                lru=c["lru"].at[ss, victim].set(t, mode="drop"))


def line_round(c, line, active, may_fill, t, waves):
    """A tag-only cache serving `waves` ordered waves of equal width in one
    cycle. Start-of-cycle tags decide first hits; a miss that may fill is a
    candidate unless the same position filled that line in an earlier
    wave; per (set, wave) the lowest candidate lane fills; the k-th filling
    wave of a set takes the k-th least recently used way of the
    start-of-cycle order (at most n_ways per set); after the fills, a lane
    that did not fill hits if its line is now present."""
    n_sets, n_ways = c["tag"].shape
    N = line.shape[0]
    C = N // waves
    s = _set_of(line, n_sets)
    match = c["tag"][s] == line[:, None]
    first_hit = match.any(1) & active
    way = jnp.argmax(match, 1)

    cand0 = (active & ~first_hit & may_fill).reshape(waves, C)
    lw = line.reshape(waves, C)
    cand = [cand0[0]]
    for w in range(1, waves):
        seen = jnp.zeros((C,), bool)
        for v in range(w):
            seen = seen | (cand0[v] & (lw[v] == lw[w]))
        cand.append(cand0[w] & ~seen)
    cand = jnp.concatenate(cand)

    wave = jnp.repeat(jnp.arange(waves, dtype=I32), C)
    fills = _first_per(s * waves + wave, cand, n_sets * waves)
    busy = jnp.zeros((n_sets * waves,), bool).at[
        jnp.where(cand, s * waves + wave, n_sets * waves)].set(
        True, mode="drop").reshape(n_sets, waves)
    rank = (busy[s] & (jnp.arange(waves)[None, :] < wave[:, None])).sum(1)
    fills = fills & (rank < n_ways)
    order = jnp.argsort(c["lru"][s], axis=1, stable=True)
    victim = jnp.take_along_axis(
        order, jnp.minimum(rank, n_ways - 1)[:, None], 1)[:, 0]

    slot = jnp.where(first_hit, s * n_ways + way, s * n_ways + victim)
    writes = _last_per(slot, first_hit | fills, n_sets * n_ways)
    tgt = jnp.where(writes, slot, n_sets * n_ways)
    tag = c["tag"].reshape(-1).at[tgt].set(line, mode="drop").reshape(
        n_sets, n_ways)
    lru = c["lru"].reshape(-1).at[tgt].set(t, mode="drop").reshape(
        n_sets, n_ways)
    late = (tag[s] == line[:, None]).any(1)
    hit = first_hit | (active & ~fills & late)
    return dict(c, tag=tag, lru=lru), hit


# ------------------------------------------------------------------ DRAM
def dram_round(cfg, d, channel, bank, row, app, is_walk, active, mask_on,
               thres_max, waves):
    """Golden (walks) / silver (one app under an Eq. (1) quota) / normal
    queues, row hits first; each wave queues on its own (channel, bank)s."""
    n_ch, n_bk = d["open_row"].shape
    na = d["conc"].shape[0]
    N = app.shape[0]
    C = N // waves
    cls = jnp.where(mask_on, jnp.where(is_walk, 0, jnp.where(
        app == d["silver_app"], 1, 2)), 2).astype(I32)
    cb = channel * n_bk + bank
    rw, cbw, aw = (row.reshape(waves, C), cb.reshape(waves, C),
                   active.reshape(waves, C))
    # a row is open if the bank holds it, or if the same position opened it
    # in an earlier wave of this cycle
    row_hit = (d["open_row"][channel, bank] == row).reshape(waves, C)
    row_hit = jnp.stack([row_hit[w] | functools.reduce(
        jnp.logical_or, [aw[v] & (rw[v] == rw[w]) & (cbw[v] == cbw[w])
                         for v in range(w)], jnp.zeros((C,), bool))
        for w in range(waves)]).reshape(N)
    service = jnp.where(row_hit, cfg["dram_row_hit"], cfg["dram_row_miss"])
    # requests ahead in this wave on my bank: lower (class, row miss) key,
    # or the same key and an earlier lane
    key = (cls * 2 + jnp.where(row_hit, 0, 1)).reshape(waves, C)
    mine, other = key[:, :, None], key[:, None, :]
    earlier = jnp.arange(C)[None, :] < jnp.arange(C)[:, None]
    ahead = ((cbw[:, None, :] == cbw[:, :, None]) & aw[:, None, :]
             & ((other < mine) | ((other == mine) & earlier[None])))
    n_ahead = ahead.sum(2).reshape(N)

    w_ = d["conc"] * d["stalled"]
    quota = jnp.maximum(thres_max * w_ // jnp.maximum(w_.sum(), 1), 1)
    wave = jnp.repeat(jnp.arange(waves), C)
    q = d["queue"]
    backlog = jnp.zeros((N,), I32)
    for w in range(waves):
        here = wave == w
        backlog = jnp.where(here, q[channel, cls], backlog)
        counts = jnp.zeros((n_ch, 3), I32).at[channel, cls].add(
            (active & here).astype(I32))
        q = (q * 3 + counts) // 4
    lat = jnp.where(active, service + (n_ahead + backlog)
                    * cfg["dram_queue_unit"], 0)

    last = _last_per(cb, active, n_ch * n_bk)
    open_row = d["open_row"].reshape(-1).at[
        jnp.where(last, cb, n_ch * n_bk)].set(row, mode="drop").reshape(
        n_ch, n_bk)
    s_app, s_left = d["silver_app"], d["silver_left"]
    for w in range(waves):
        left = s_left - (aw[w] & (cls.reshape(waves, C)[w] == 1)).sum(
            dtype=I32)
        nxt = (s_app + 1) % na
        s_app, s_left = (jnp.where(left <= 0, nxt, s_app),
                         jnp.where(left <= 0, quota[nxt], left))
    return dict(d, open_row=open_row, silver_app=s_app, silver_left=s_left,
                queue=q), lat


# ------------------------------------------------------------ the model
def _geometry(cfg):
    C, na = cfg["n_cores"], cfg["n_apps"]
    app_of_core = np.array([(c * na) // C for c in range(C)], np.int32)
    cores = np.bincount(app_of_core, minlength=na).astype(np.int32)
    return app_of_core, cores, cores * cfg["warps_per_core"]


def init_state(cfg, design, acc_dtype):
    C, wpc, na = cfg["n_cores"], cfg["warps_per_core"], cfg["n_apps"]
    _, _, warps = _geometry(cfg)

    def cache(entries, ways, asids=True):
        shape = (max(entries // ways, 1), ways)
        c = dict(tag=jnp.full(shape, -1, I32), lru=jnp.zeros(shape, I32))
        if asids:
            c["asid"] = jnp.full(shape, -1, I32)
        return c

    l1 = cache(cfg["l1_tlb_entries"], cfg["l1_tlb_entries"])
    frac = jnp.float32(cfg["initial_token_frac"])
    wt = cfg["max_concurrent_walks"]
    return dict(
        t=jnp.zeros((), I32),
        stall=jnp.zeros((C * wpc,), I32), pos=jnp.zeros((C * wpc,), I32),
        instr=jnp.zeros((C * wpc,), acc_dtype),
        l1={k: jnp.broadcast_to(v, (C,) + v.shape[1:]) for k, v in l1.items()},
        l2tlb=cache(cfg["l2_tlb_entries"], cfg["l2_tlb_ways"]),
        byp=cache(cfg["bypass_cache_entries"], cfg["bypass_cache_entries"]),
        pwc=cache(cfg["pwc_entries"], cfg["pwc_ways"], asids=False),
        l2c=cache(cfg["l2_sets"] * cfg["l2_ways"], cfg["l2_ways"],
                  asids=False),
        walk=dict(vpn=jnp.full((wt,), -1, I32), asid=jnp.full((wt,), -1, I32),
                  done=jnp.zeros((wt,), I32), merged=jnp.zeros((wt,), I32)),
        dram=dict(open_row=jnp.full((cfg["n_channels"], cfg["n_banks"]), -1,
                                    I32),
                  silver_app=jnp.zeros((), I32), silver_left=jnp.ones((), I32),
                  conc=jnp.zeros((na,), I32), stalled=jnp.zeros((na,), I32),
                  queue=jnp.zeros((cfg["n_channels"], 3), I32)),
        bp=dict(hits=jnp.zeros((8,), I32), acc=jnp.zeros((8,), I32),
                rate=jnp.zeros((8,), I32), have=jnp.array(False),
                epoch=jnp.zeros((), I32)),
        tok=dict(n=jnp.maximum((jnp.asarray(warps) * frac).astype(I32), 1),
                 dir=jnp.full((na,), -1, I32),
                 prev=jnp.ones((na,), jnp.float32),
                 hits=jnp.zeros((na,), I32), misses=jnp.zeros((na,), I32),
                 first=jnp.array(True)),
        ints=jnp.zeros((na, 9), I32), floats=jnp.zeros((na, 4), acc_dtype),
        shared=jnp.zeros((4,), I32),
    )


def _static_index(index, n, na, app):
    start = (app * n) // na
    span = jnp.maximum((app + 1) * n // na - start, 1)
    return jnp.minimum(start + index % span, n - 1)


def cycle(cfg, design, params, s):
    """One simulated cycle of the whole GPU; `params` is (n_apps, fields)."""
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
    asid = app

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
                bp=bp, tok=tok, ints=ints, floats=floats, shared=shared)


@functools.lru_cache(maxsize=None)
def _program(cfg_key, design_key, cycles, acc_name):
    import json
    cfg, design = json.loads(cfg_key), json.loads(design_key)
    acc = jnp.dtype(acc_name)

    def one(params):
        def body(s, _):
            return cycle(cfg, design, params, s), None
        final, _ = jax.lax.scan(body, init_state(cfg, design, acc), None,
                                length=cycles)
        return {k: final[k] for k in ("t", "instr", "ints", "floats",
                                      "shared")} | {"tokens": final["tok"]["n"]}

    return jax.jit(jax.vmap(one))


def simulate(cfg, design_name, rows, cycles, acc_dtype="float32"):
    """Final counters of each row ((R, n_apps, fields) app parameters) after
    `cycles` cycles under one design; a dict of numpy arrays with a leading
    row axis."""
    import json
    keep = {k: v for k, v in cfg.items()
            if k not in ("apps", "app_category", "assumed", "guarantees",
                         "designs", "deployment", "source", "name")}
    run = _program(json.dumps(keep, sort_keys=True),
                   json.dumps(cfg["designs"][design_name], sort_keys=True),
                   int(cycles), str(acc_dtype))
    rows = np.asarray(rows, np.int32)
    # rows padded to a power of two, so the row counts that seeds draw share
    # a few programs, which later runs find in the persistent cache
    n = len(rows)
    padded = np.concatenate([rows, np.repeat(rows[:1], (1 << (n - 1)
                                                        .bit_length()) - n, 0)])
    final = jax.device_get(run(jnp.asarray(padded)))
    return jax.tree_util.tree_map(lambda x: x[:n], final)


def app_rows(cfg, benches):
    """(n_apps, fields) parameters of a mix; None is the idle partner."""
    return np.array([cfg["apps"][b] if b is not None else cfg["idle_app"]
                     for b in benches], np.int32)


def stats(cfg, final, r, dtype=np.float64):
    """The per-app statistics of row `r`, as the paper's tables define them,
    derived on the host in `dtype` (the configuration states float64)."""
    na = cfg["n_apps"]
    app_of_core, _, _ = _geometry(cfg)
    warp_app = np.repeat(app_of_core, cfg["warps_per_core"])
    t = dtype(final["t"][r])
    instr = np.asarray(final["instr"][r], dtype)
    ipc = np.array([instr[warp_app == a].sum() for a in range(na)],
                   dtype) / t
    i = np.asarray(final["ints"][r], dtype)
    f = np.asarray(final["floats"][r], dtype)
    sh = np.asarray(final["shared"][r], dtype)

    def ratio(a, b):
        return a / np.maximum(b, dtype(1))

    l1p, l2p = i[:, 0] + i[:, 1], i[:, 2] + i[:, 3]
    return {
        "ipc": ipc,
        "l1_hit_rate": ratio(i[:, 0], l1p), "l1_miss_rate": ratio(i[:, 1], l1p),
        "l2_hit_rate": ratio(i[:, 2], l2p), "l2_miss_rate": ratio(i[:, 3], l2p),
        "byp_hit_rate": ratio(i[:, 4], i[:, 5]),
        "walk_lat": ratio(f[:, 0], i[:, 6]), "walks": i[:, 6],
        "stalls_per_miss": ratio(f[:, 1], i[:, 6]),
        "dram_tlb_lat": ratio(f[:, 2], i[:, 7]),
        "dram_data_lat": ratio(f[:, 3], i[:, 8]),
        "dram_tlb_n": i[:, 7], "dram_data_n": i[:, 8],
        "l2c_tlb_hit_rate": ratio(sh[0], sh[1]),
        "l2c_data_hit_rate": ratio(sh[2], sh[3]),
        "tokens": np.asarray(final["tokens"][r]),
        "cycles": float(t),
    }
