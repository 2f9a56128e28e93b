"""KernelConfig — one frozen launch-config object for the W1A8 kernels.

Collapses the per-call kwargs that used to be scattered over
``w1a8_matmul`` / ``w1a8_conv3x3`` / ``w1a8_conv3x3_pool`` (``accum``,
``out_step``, ``interpret``, ``use_kernel``, implicit tile picks) into one
hashable dataclass that jit treats as a static argument, plus the
resolution machinery that turns an (op, layer shape, accum, device) cell
into a concrete config:

    exact autotune-table hit  →  nearest-shape fallback  →  heuristics

The committed table lives at ``benchmarks/results/AUTOTUNE_kernels.json``
(``REPRO_AUTOTUNE_TABLE`` overrides; produced by ``repro.launch.autotune``).
Every table winner is bit-exact vs the heuristic default by construction —
tile/row blocking never changes the per-row dot operands, only the launch
grid — so resolution is a pure perf decision (DESIGN.md §13).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import pathlib
import warnings
from typing import Dict, Optional, Sequence, Tuple

OPS = ("matmul", "conv3x3", "conv3x3_pool")
ACCUMS = ("dot", "popcount")

# Heuristic tile preferences (the former ops.py `_pick` constants).
DEF_BM, DEF_BK, DEF_BN = 256, 512, 256
PACK = 32  # mirrors core.packing.PACK without importing jax at module load


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_tile(dim: int, pref: int, mult: int) -> int:
    """Largest tile ≤ pref that keeps padding small; multiple of `mult`."""
    if dim >= pref:
        return pref
    return max(mult, _round_up(dim, mult))


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Launch configuration for one W1A8 kernel call.

    Frozen + hashable ⇒ usable directly as a jit static argument; two
    configs that launch identically compare/hash equal (``source`` is
    provenance only and excluded from eq/hash).

    ``interpret=None`` resolves at call time to "am I off-TPU?" —
    ``True`` on the CPU backend, ``False`` otherwise. ``bm/bn/bk=None``
    fall back to the `pick_tile` heuristics at the call site. ``rows`` is
    the conv/fused-pool row-blocking factor (output rows — pooled rows
    for the fused kernel — produced per grid step); the ops layer clips
    it to a divisor of the row count. ``fused`` routes
    ``w1a8_conv3x3_pool`` through the single fused kernel (True) or
    conv-then-reduce_window (False); both routes admit both accum modes
    (the fused kernel has dot and popcount datapaths). All field
    validation happens here at construction — dispatch never rejects a
    config that constructed cleanly.
    """

    op: str = "matmul"
    accum: str = "dot"
    out_step: Optional[float] = None
    interpret: Optional[bool] = None
    use_kernel: bool = True
    bm: Optional[int] = None
    bn: Optional[int] = None
    bk: Optional[int] = None
    rows: int = 1
    fused: bool = True
    source: str = dataclasses.field(default="manual", compare=False)

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"op must be one of {OPS}, got {self.op!r}")
        if self.accum not in ACCUMS:
            raise ValueError(
                f"accum must be one of {ACCUMS}, got {self.accum!r}")
        if self.bk is not None and self.bk % PACK:
            raise ValueError(f"bk must be a multiple of {PACK}, got {self.bk}")
        if self.rows < 1:
            raise ValueError(f"rows must be ≥ 1, got {self.rows}")

    # -- call-time resolution ------------------------------------------------

    def resolved_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        import jax
        return jax.default_backend() != "tpu"

    def matmul_tiles(self, m: int, k: int, n: int) -> Tuple[int, int, int]:
        """(bm, bk, bn) with heuristics filling any unset field."""
        bm = self.bm if self.bm is not None else pick_tile(m, DEF_BM, 8)
        bk = self.bk if self.bk is not None else pick_tile(k, DEF_BK, PACK)
        bn = self.bn if self.bn is not None else pick_tile(n, DEF_BN, 128)
        return bm, bk, bn

    def conv_rows(self, h: int) -> int:
        """Largest divisor of `h` that is ≤ self.rows (≥ 1)."""
        r = max(1, min(self.rows, h))
        while h % r:
            r -= 1
        return r

    def replace(self, **kw) -> "KernelConfig":
        return dataclasses.replace(self, **kw)

    # -- persistence ---------------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


# -- shape keys + device ----------------------------------------------------
#
# conv3x3 / conv3x3_pool dims: (h, w, cin, cout) of the *input* plane;
# matmul dims: (m, k, n) with batch folded into m. Batch is deliberately
# not part of the key: the conv grid is parallel over batch and the matmul
# folds it into m, so the structural cell is batch-free.


def device_key() -> str:
    import jax
    kind = jax.devices()[0].device_kind
    return str(kind).strip().lower().replace(" ", "-")


def shape_key(op: str, dims: Sequence[int], accum: str,
              device: Optional[str] = None) -> str:
    dev = device if device is not None else device_key()
    return f"{op}/{'x'.join(str(int(d)) for d in dims)}/{accum}/{dev}"


def parse_key(key: str) -> Tuple[str, Tuple[int, ...], str, str]:
    op, dims, accum, dev = key.split("/", 3)
    return op, tuple(int(d) for d in dims.split("x")), accum, dev


# -- autotune table ---------------------------------------------------------

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_TABLE = _REPO_ROOT / "benchmarks" / "results" / "AUTOTUNE_kernels.json"

_table_cache: Dict[str, Optional[dict]] = {}


def table_path() -> pathlib.Path:
    return pathlib.Path(os.environ.get("REPRO_AUTOTUNE_TABLE",
                                       str(DEFAULT_TABLE)))


def load_table(path: Optional[os.PathLike] = None) -> dict:
    """entries dict (key → record) from the autotune table; {} if absent."""
    p = pathlib.Path(path) if path is not None else table_path()
    ck = str(p)
    if ck not in _table_cache:
        try:
            with open(p) as f:
                _table_cache[ck] = json.load(f).get("entries", {})
        except (OSError, json.JSONDecodeError):
            _table_cache[ck] = {}
    return _table_cache[ck]


def clear_table_cache() -> None:
    _table_cache.clear()


def _shape_distance(a: Sequence[int], b: Sequence[int]) -> float:
    if len(a) != len(b):
        return math.inf
    return sum(abs(math.log(max(x, 1) / max(y, 1))) for x, y in zip(a, b))


def resolve(op: str, dims: Sequence[int], *, accum: str = "dot",
            device: Optional[str] = None,
            table: Optional[dict] = None) -> KernelConfig:
    """Table lookup → nearest-shape fallback → heuristic default.

    Nearest-shape: among same-(op, accum, device) entries, minimal
    log-space distance over dims; ties break on the lexicographically
    smallest key so resolution is deterministic.
    """
    dev = device if device is not None else device_key()
    entries = table if table is not None else load_table()
    key = shape_key(op, dims, accum, dev)
    hit = entries.get(key)
    if hit is not None:
        return KernelConfig.from_dict(
            {**hit["config"], "source": "table"})
    best = None
    for k, rec in entries.items():
        try:
            kop, kdims, kaccum, kdev = parse_key(k)
        except ValueError:
            continue
        if (kop, kaccum, kdev) != (op, accum, dev):
            continue
        d = _shape_distance(dims, kdims)
        if best is None or (d, k) < (best[0], best[1]):
            best = (d, k, rec)
    if best is not None and math.isfinite(best[0]):
        return KernelConfig.from_dict(
            {**best[2]["config"], "source": "nearest"})
    return KernelConfig(op=op, accum=accum, source="heuristic")


def resolve_tuned(op: str, dims: Sequence[int], *,
                  allow_popcount: bool = True,
                  device: Optional[str] = None,
                  table: Optional[dict] = None) -> KernelConfig:
    """Pick the fastest accum variant for the cell, then resolve its config.

    Compares exact-key ``t_us`` across accum modes (``allow_popcount=False``
    restricts to dot for callers that want to opt out); without exact
    entries for both modes it resolves the dot config normally. Popcount is
    always *eligible*: per-channel operands are honoured via the
    uniform-step fold (`core.quant.fold_codes_to_uniform_step`).
    """
    dev = device if device is not None else device_key()
    entries = table if table is not None else load_table()
    accums = ACCUMS if allow_popcount else ("dot",)
    timed = []
    for acc in accums:
        rec = entries.get(shape_key(op, dims, acc, dev))
        if rec is not None and "t_us" in rec:
            timed.append((rec["t_us"], acc))
    accum = min(timed)[1] if timed else "dot"
    return resolve(op, dims, accum=accum, device=dev, table=entries)


# -- legacy-kwarg shim -------------------------------------------------------

_UNSET = object()

# Warn exactly once per process (the ServeEngine pattern); tests reset this
# to re-arm the warning.
_deprecation_warned = False


def _warn_legacy_once() -> None:
    global _deprecation_warned
    if _deprecation_warned:
        return
    _deprecation_warned = True
    warnings.warn(
        "per-call kernel kwargs (accum=/out_step=/interpret=/use_kernel=) "
        "are deprecated; pass config=KernelConfig(...) instead",
        DeprecationWarning, stacklevel=4)


def normalize(op: str, config: Optional[KernelConfig],
              **legacy) -> KernelConfig:
    """Merge a ``config=`` object with legacy per-call kwargs.

    ``config`` given → legacy kwargs must all be unset (TypeError
    otherwise) and ``config.op`` must match. No config → a KernelConfig is
    built from the legacy kwargs (warning once per process if any were
    passed explicitly), preserving each op's historical defaults.
    """
    passed = {k: v for k, v in legacy.items() if v is not _UNSET}
    if config is not None:
        if passed:
            raise TypeError(
                f"pass either config= or legacy kwargs, not both "
                f"(got config and {sorted(passed)})")
        if config.op != op:
            raise ValueError(
                f"config.op={config.op!r} does not match the "
                f"{op!r} entry point")
        return config
    if passed:
        _warn_legacy_once()
    defaults = {}
    if op == "conv3x3_pool":
        defaults["out_step"] = 1.0
    defaults.update(passed)
    return KernelConfig(op=op, source="legacy" if passed else "default",
                        **defaults)
