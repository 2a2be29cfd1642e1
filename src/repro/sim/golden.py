"""Pinned float-hex goldens: the simulator's bit-for-bit reference.

Golden stats for the pinned mix 3DS+BLK under the lane-fused memory
path, full paper Table 1 size (`SimConfig` defaults). `float.hex()`
encoding keeps the comparison bit-for-bit, not approximate. The
`mask@9000` entry crosses an epoch boundary (epoch_cycles=8000) so the
token hill-climb, bypass latch, and DRAM pressure-update paths are all
pinned too.

The same table is checked on every backend: on the CPU by
`tests/test_memsys_stages.py`, on a TPU by `chip_smoke.py`. A simulator
that answers differently on its test platform and on the chip would
make every CPU test meaningless. Any intentional semantic change must
re-capture these AND bump `benchmarks/paper_repro.CACHE_VERSION` (see
README "Changing simulator semantics intentionally").
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from repro.sim.runner import run_mix

GOLDEN_MIX = ("3DS", "BLK")
GOLDEN_CYCLES = 1_200

GOLDEN = {
    'ideal': {
        'ipc': ['0x1.490aaaaaaaaabp+7', '0x1.5b4e81b4e81b5p+5'],
        'l2_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'walk_lat': ['0x0.0p+0', '0x0.0p+0'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x0.0p+0'],
    },
    'pwc': {
        'ipc': ['0x1.4e80000000000p+6', '0x1.bbd0369d0369dp+3'],
        'l2_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'walk_lat': ['0x1.5026f7e1b0fb2p+7', '0x1.5aaa0a82a0a83p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.cb5d4ef40991fp-7'],
    },
    'gpu-mmu': {
        'ipc': ['0x1.642aaaaaaaaabp+6', '0x1.0951eb851eb85p+4'],
        'l2_hit_rate': ['0x1.54629b7f0d463p-2', '0x1.ce36b4175b466p-3'],
        'walk_lat': ['0x1.9d6e4630d013fp+7', '0x1.52af50af50af5p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c94f90a5867d4p-1'],
    },
    'static': {
        'ipc': ['0x1.64aaaaaaaaaabp+6', '0x1.0951eb851eb85p+4'],
        'l2_hit_rate': ['0x1.5555555555555p-2', '0x1.d86d35d69602cp-3'],
        'walk_lat': ['0x1.9b3ae2a572bf1p+7', '0x1.5253aa554440ep+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c90abcc0242afp-1'],
    },
    'mask': {
        'ipc': ['0x1.62c0000000000p+6', '0x1.08bbbbbbbbbbcp+4'],
        'l2_hit_rate': ['0x1.53bd02647c694p-2', '0x1.d0d68a67435a3p-3'],
        'walk_lat': ['0x1.a000000000000p+7', '0x1.53c5f46414040p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c922d719c060fp-1'],
    },
    'mask-tlb': {
        'ipc': ['0x1.642aaaaaaaaabp+6', '0x1.0951eb851eb85p+4'],
        'l2_hit_rate': ['0x1.54629b7f0d463p-2', '0x1.ce36b4175b466p-3'],
        'walk_lat': ['0x1.9d6e4630d013fp+7', '0x1.52af50af50af5p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c94f90a5867d4p-1'],
    },
    'mask-cache': {
        'ipc': ['0x1.642aaaaaaaaabp+6', '0x1.0951eb851eb85p+4'],
        'l2_hit_rate': ['0x1.54629b7f0d463p-2', '0x1.ce36b4175b466p-3'],
        'walk_lat': ['0x1.9d6e4630d013fp+7', '0x1.52af50af50af5p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c94f90a5867d4p-1'],
    },
    'mask-dram': {
        'ipc': ['0x1.62c0000000000p+6', '0x1.08bbbbbbbbbbcp+4'],
        'l2_hit_rate': ['0x1.53bd02647c694p-2', '0x1.d0d68a67435a3p-3'],
        'walk_lat': ['0x1.a000000000000p+7', '0x1.53c5f46414040p+8'],
        'byp_hit_rate': ['0x0.0p+0', '0x0.0p+0'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.c922d719c060fp-1'],
    },
    'mask@9000': {
        'ipc': ['0x1.712aaaaaaaaabp+6', '0x1.5575a56ed1ce6p+4'],
        'l2_hit_rate': ['0x1.3aab8f24fb8c7p-2', '0x1.06a395c6a395cp-2'],
        'walk_lat': ['0x1.36f44b13ee32bp+7', '0x1.76877d6dc735ep+7'],
        'byp_hit_rate': ['0x1.0d29dde11c5eep-6', '0x1.6067bb6ff2802p-8'],
        'tokens': ['0x1.e000000000000p+6', '0x1.e000000000000p+6'],
        'l2c_tlb_hit_rate': ['0x1.de0d0f208e060p-1'],
    },
}


def golden_mismatches(entry: str) -> Dict[str, Tuple[List[str], List[str]]]:
    """Run one golden entry (`"mask@9000"` runs 9000 cycles, a bare
    design name GOLDEN_CYCLES) on the default backend; returns
    {key: (got, want)} for every stat whose float-hex differs (empty
    when the entry matches bit for bit)."""
    name, _, cyc = entry.partition("@")
    s = run_mix(name, list(GOLDEN_MIX),
                cycles=int(cyc) if cyc else GOLDEN_CYCLES)
    out = {}
    for key, want in GOLDEN[entry].items():
        got = [x.hex() for x in
               np.asarray(s[key], np.float64).ravel().tolist()]
        if got != want:
            out[key] = (got, want)
    return out
