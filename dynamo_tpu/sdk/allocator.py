"""Per-service accelerator assignment (reference: sdk cli/allocator.py:33-99
— the CUDA_VISIBLE_DEVICES math; here the unit is TPU chips).

One process per chip set: the supervisor (sdk/serve.py) never touches JAX
and hands each service worker a disjoint chip set through the environment
libtpu reads. A service that declares ``resources={"tpu": n}`` owns an
engine and gets n chips; a service with no "tpu" resource is host-only
(frontend, processor, router) and gets JAX_PLATFORMS=cpu so it never grabs
a chip. Where the operator's own environment says JAX_PLATFORMS=cpu the
whole graph runs on the CPU (demos, tests): engine services inherit that
and no chip is assigned. The allocator itself never sends an engine
service to the CPU.
"""
from __future__ import annotations

from typing import Dict, List

# chips per process -> TPU_CHIPS_PER_PROCESS_BOUNDS (x,y,z) on a v5e host,
# whose chips number row-major over a 2-wide grid (chips 0,1 are one row).
# Each entry was run on a four-chip v5e (2x2) host; a count with no entry
# has no layout known to work, and asking for it is an error.
CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1"}


class ChipAllocator:
    def __init__(self, total_chips: int, host_is_cpu: bool = False):
        self.total = total_chips
        self.host_is_cpu = host_is_cpu
        self._next = 0

    def assign(self, n: int) -> List[int]:
        if n not in CHIP_BOUNDS:
            raise RuntimeError(
                f"no chip layout for a {n}-chip process "
                f"(have {sorted(CHIP_BOUNDS)})")
        # an n-chip block starts on a multiple of n, so it is a whole
        # row / the whole grid and never straddles two rows
        start = -(-self._next // n) * n
        if start + n > self.total:
            raise RuntimeError(
                f"not enough TPU chips: need {n}, "
                f"{max(self.total - start, 0)} of {self.total} left "
                f"(pass --tpu-chips, or set JAX_PLATFORMS=cpu to run the "
                f"graph on the CPU)")
        self._next = start + n
        return list(range(start, start + n))

    def env_for(self, resources: Dict) -> Dict[str, str]:
        n = int(resources.get("tpu", 0))
        if n <= 0:
            # host-only service: keep it off the chips entirely
            return {"JAX_PLATFORMS": "cpu"}
        if self.host_is_cpu:
            return {}
        chips = self.assign(n)
        return {
            "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": CHIP_BOUNDS[n],
            # each engine process is its own one-process TPU system: no
            # libtpu rendezvous with the sibling processes on this host
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
