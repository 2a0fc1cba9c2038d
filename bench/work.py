"""Work of one H-matrix apply, counted from the plan's shapes (never from
the compiled program), and the chip peaks that turn work into a least
time.

An apply of an (n, R) panel in P mode does, per admissible level group of
B blocks of m rows at rank k, ``V^T X`` and ``U T``: 4 B m k R FLOPs, and
reads the level's U and V once (2 B m k words).  The dense leaves are B_d
blocks of c x c: 2 B_d c^2 R FLOPs on the MXU; their kernel entries are
regenerated on the vector units, which the MXU peak does not count, so
they add no FLOPs here.  The least bytes are the store, the points, the
panel read once and the result written once.
"""
from __future__ import annotations

from dataclasses import dataclass

WORD = 4            # float32

# Keyed by ``jax.devices()[0].device_kind``; copied from the library's
# ``analysis/roofline.py`` so that later changes there cannot move it.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s"},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Shapes:
    """What the work depends on: the plan's block counts and sizes."""
    n: int                      # points
    n_pad: int                  # padded to a power of two
    d: int                      # dimension
    k: int                      # ACA rank
    c_leaf: int
    aca_blocks: dict            # level -> number of admissible blocks
    dense_blocks: int           # inadmissible leaf blocks

    @classmethod
    def of_plan(cls, plan, n: int, d: int, k: int) -> "Shapes":
        return cls(n=n, n_pad=int(plan.n_pad), d=d, k=k,
                   c_leaf=int(plan.c_leaf),
                   aca_blocks={int(lv): int(b.shape[0])
                               for lv, b in plan.aca_levels.items()},
                   dense_blocks=int(plan.dense_blocks.shape[0]))

    def store_bytes(self) -> int:
        """Bytes of the stored U and V of every level group."""
        return sum(2 * b * (self.n_pad >> lv) * self.k * WORD
                   for lv, b in self.aca_blocks.items())


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, device_kind: str) -> tuple[float, str]:
        """Least time on one chip and the bound that sets it."""
        p = peaks(device_kind)
        compute = self.flops / p["flops"]
        memory = self.bytes / p["hbm_bytes_per_s"]
        return (compute, "compute") if compute >= memory else (memory, "memory")


def apply_work(s: Shapes, r: int) -> Work:
    """One product of the H-matrix with an (n, r) panel."""
    lowrank = sum(4 * b * (s.n_pad >> lv) * s.k * r
                  for lv, b in s.aca_blocks.items())
    dense = 2 * s.dense_blocks * s.c_leaf ** 2 * r
    nbytes = (s.store_bytes() + s.n_pad * s.d * WORD
              + 2 * s.n * r * WORD)
    return Work(float(lowrank + dense), float(nbytes))


def roofline_pct(work: Work, device_s: float, device_kind: str):
    """Share of the least time in the measured device time, in %, and the
    bound; None where nothing was measured."""
    if not device_s or device_s <= 0:
        return None
    least, bound = work.least_s(device_kind)
    return 100.0 * least / device_s, bound
