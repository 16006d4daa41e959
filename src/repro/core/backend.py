"""Unified execution-backend layer: every kernel-vs-host decision in one
place, measured instead of guessed.

The engine's data plane has three ways to run each launch:

* ``host``      — vectorized numpy (the packed-sort k-way merge, the
  bit-twiddling Bloom probe over the host filter-stack mirror).  The CPU
  fast path: no dispatch overhead, no interpreter.
* ``interpret`` — the Pallas kernels on the Pallas interpreter.  A
  correctness harness (bit-identical to compiled lowering by
  construction), never a fast path.
* ``compiled``  — the Pallas kernels compiled for the local XLA backend.
  Unavailable on CPU XLA builds that only support interpret mode;
  ``compiled_supported()`` probes once per process.

Historically the choice was a "CPU-means-host" guess spread across three
engine booleans (``use_kernels``, ``interpret``, ``scan_use_kernels``)
re-interpreted at every call site.  ``ExecBackend`` owns the decision:
it exposes the four data-plane entry points (``probe_multi``,
``merge_kway``, ``merge_kway_window``, ``scan_merge``), carries the
interpret/compiled mode, and — in ``auto`` mode — picks host vs kernel
*per op per size class* from a MEASURED crossover table: the
``benchmarks/kernels_bench.py`` sweep times every available mode at a
grid of sizes and persists the fastest per (op, size) to
``artifacts/bench/backend_calibration.json``, which engines load at
construction.  With no calibration artifact the built-in default applies
(compiled when supported, else host — the interpreter never wins a
performance decision).

On a TPU (``jax.default_backend() == "tpu"``) every data-plane op runs
``compiled`` unless the caller forced a mode: no calibration, no
fallback and no environment switch can move the chip's data plane onto
the host or the interpreter, and a kernel the chip refuses raises.  A
calibration table records the ``device_kind`` it was measured on and is
applied only on that kind of device.

The three legacy engine booleans survive as thin deprecated overrides:
``ExecBackend.from_legacy`` maps them to FORCED per-op modes that
reproduce the historical dispatch, with the kernel mode compiled
wherever compiled Pallas is supported unless ``interpret=True`` is
passed explicitly.

All three modes are pinned bit-identical on merge/probe/scan results by
``tests/test_backend.py`` (compiled skipped where unsupported).
"""
from __future__ import annotations

import functools
import json
from bisect import bisect_right
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.bloom.ops import bloom_probe_multi, bloom_probe_multi_host
from repro.kernels.merge.ops import merge_dedup_kway

from .memtable import drop_tombstones

HOST, INTERPRET, COMPILED = "host", "interpret", "compiled"
MODES = (HOST, INTERPRET, COMPILED)
#: ops the backend dispatches; ``merge_kway_window`` shares
#: ``merge_kway``'s calibration entry when it has none of its own.
OPS = ("probe_multi", "merge_kway", "merge_kway_window", "scan_merge")
_OP_ALIAS = {"merge_kway_window": "merge_kway"}

#: default calibration artifact (written by ``benchmarks/kernels_bench``)
CALIBRATION_PATH = Path(__file__).resolve().parents[3] / "artifacts" / \
    "bench" / "backend_calibration.json"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: the data plane then
    runs compiled, whatever a calibration table says."""
    return jax.default_backend() == "tpu"


def device_kind() -> str:
    """``device_kind`` of the default device (calibration tables are
    keyed by it)."""
    return jax.devices()[0].device_kind


@functools.lru_cache(maxsize=1)
def compiled_supported() -> bool:
    """Can this process lower a Pallas kernel for real (interpret=False)?

    False on a CPU backend, whose XLA build only runs the interpreter.
    Elsewhere a one-tile copy kernel is compiled and run once, and any
    lowering error PROPAGATES: a chip that refuses a kernel must fail
    loudly, never quietly degrade the data plane to the host.
    """
    if jax.default_backend() == "cpu":
        return False

    def _copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    x = jnp.zeros((8, 128), jnp.uint32)
    out = pl.pallas_call(
        _copy, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.uint32),
        interpret=False)(x)
    jax.block_until_ready(out)
    return True


def merge_kway_host(runs) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized host k-way newest-wins merge: pack each entry as
    ``key << 32 | global_index`` (runs concatenated newest-first, so a
    lower index means a newer version), one uint64 sort, then keep the
    first entry of each equal-key group and gather only the surviving
    values.  No per-entry Python — this is the CPU fast path the
    interpret-mode Pallas tournament cannot be."""
    ks = np.concatenate([np.asarray(r[0]) for r in runs])
    n = len(ks)
    comp = (ks.astype(np.uint64) << np.uint64(32)) \
        | np.arange(n, dtype=np.uint64)
    comp.sort()
    sk = (comp >> np.uint64(32)).astype(np.uint32)
    first = np.ones(n, bool)
    first[1:] = sk[1:] != sk[:-1]
    idx = (comp[first] & np.uint64(0xFFFFFFFF)).astype(np.int64)
    vs = np.concatenate([np.asarray(r[1]) for r in runs])
    return sk[first], vs[idx]


# ----------------------------------------------------------- calibration
def write_calibration(table: dict, path: Path | str | None = None) -> Path:
    """Persist a crossover table (the ``kernels_bench`` sweep's output).

    ``table`` must carry ``{"ops": {op: {"sizes": [...], "best": [...],
    "ms": {mode: [...]}}}}``; metadata keys ride along verbatim, and
    ``device_kind`` defaults to the device this process measured on."""
    path = Path(path) if path is not None else CALIBRATION_PATH
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = dict(table)
    payload.setdefault("version", 1)
    payload.setdefault("device_kind", device_kind())
    path.write_text(json.dumps(payload, indent=2, sort_keys=True))
    return path


def load_calibration(path: Path | str | None = None) -> Optional[dict]:
    """Load the crossover table; None when absent or unreadable (the
    backend then uses its built-in default — a missing artifact must
    never fail engine construction).  Whether the table applies to this
    device is the backend's decision (see ``ExecBackend``)."""
    path = Path(path) if path is not None else CALIBRATION_PATH
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict) or "ops" not in data:
        return None
    return data


class ExecBackend:
    """One object owning every kernel-vs-host decision the engine makes.

    ``mode`` selects the dispatch discipline:

    * ``"auto"``      — compiled on a TPU; elsewhere per op per size
      class from the measured crossover table (``calibration``; loaded
      from the committed artifact when not given, and applied only when
      its ``device_kind`` is this device's), with a built-in default when
      no table applies.
    * ``"host"`` / ``"interpret"`` / ``"compiled"`` — force every op to
      one mode (differential tests and the calibration sweep use this).

    ``from_legacy`` maps the engine's three historical booleans
    (``use_kernels``, ``interpret``, ``scan_use_kernels``) to forced
    per-op modes — the deprecated compatibility surface.

    Kernel modes stage the host windows they are given onto the device
    and return host numpy results; ``merge_kway``/``merge_kway_window``
    also say which mode ran, so the engine's streaming merge knows whether
    its output came from the device.
    """

    def __init__(self, mode: str = "auto",
                 calibration: dict | Path | str | None = None,
                 merge_block: int = 256,
                 forced: Optional[dict] = None):
        if mode not in ("auto",) + MODES:
            raise ValueError(f"unknown backend mode {mode!r}")
        self.mode = mode
        self.merge_block = int(merge_block)
        self._forced: dict[str, str] = dict(forced or {})
        if mode in MODES:
            for op in OPS:
                self._forced.setdefault(op, mode)
        if COMPILED in self._forced.values() and not compiled_supported():
            raise ValueError("compiled Pallas is not supported by this "
                             "XLA backend (compiled_supported() is False)")
        if isinstance(calibration, (str, Path)):
            calibration = load_calibration(calibration)
        elif calibration is None and mode == "auto" and not self._forced:
            calibration = load_calibration()
        if calibration is not None and \
                calibration.get("device_kind") != device_kind():
            calibration = None        # measured on another kind of device
        self.calibration = calibration
        # legacy-compat reporting flags (engine properties read these)
        self.legacy_use_kernels: Optional[bool] = None
        self.legacy_scan_use_kernels: Optional[bool] = None

    # ------------------------------------------------------------- legacy
    @classmethod
    def from_legacy(cls, use_kernels: bool = True,
                    interpret: Optional[bool] = None,
                    scan_use_kernels: Optional[bool] = None,
                    merge_block: int = 256) -> "ExecBackend":
        """DEPRECATED mapping of the three historical engine booleans to
        forced per-op modes:

        * the kernel mode is ``interpret`` only when ``interpret=True`` is
          passed; otherwise compiled where supported, else interpret;
        * merges: kernel iff ``use_kernels``;
        * probe: always the fused kernel;
        * scans: ``scan_use_kernels`` — None (auto) means kernel only
          when compiled, True/False force a side.
        """
        if interpret is None:
            interpret = not compiled_supported()
        kmode = INTERPRET if interpret else COMPILED
        use_kernels = bool(use_kernels)
        if scan_use_kernels is None:
            scan_kernel = use_kernels and not interpret
        else:
            scan_kernel = bool(scan_use_kernels)
        forced = {
            "probe_multi": kmode,
            "merge_kway": kmode if use_kernels else HOST,
            "merge_kway_window": kmode if use_kernels else HOST,
            "scan_merge": kmode if scan_kernel else HOST,
        }
        be = cls(mode="auto", merge_block=merge_block, forced=forced)
        be.legacy_use_kernels = use_kernels
        be.legacy_scan_use_kernels = scan_kernel
        return be

    # ------------------------------------------------------------ decision
    def _default_mode(self) -> str:
        return COMPILED if compiled_supported() else HOST

    def decide(self, op: str, size: int) -> str:
        """The dispatch decision for one launch: which mode runs ``op``
        over ``size`` elements.  Forced modes (legacy booleans, forced
        backend) win; on a TPU everything else is compiled; otherwise the
        measured crossover table's best mode for the nearest size class
        at or below ``size``, or the built-in default.  Off the TPU a
        ``compiled`` table verdict this process cannot lower degrades to
        the next measured-best mode."""
        mode = self._forced.get(op)
        if mode is not None:
            return mode
        if on_tpu():
            return COMPILED
        return self._lookup(op, size)

    def _lookup(self, op: str, size: int) -> str:
        cal = self.calibration
        tab = None
        if cal is not None:
            ops = cal.get("ops", {})
            tab = ops.get(op) or ops.get(_OP_ALIAS.get(op, op))
        sizes = (tab or {}).get("sizes") or []
        best = (tab or {}).get("best") or []
        if not sizes or len(best) != len(sizes):
            return self._default_mode()
        i = max(0, min(bisect_right(sizes, int(size)) - 1, len(sizes) - 1))
        mode = best[i]
        if mode == COMPILED and not compiled_supported():
            ms = tab.get("ms", {})
            live = [(ms[m][i], m) for m in (HOST, INTERPRET)
                    if m in ms and ms[m] is not None
                    and ms[m][i] is not None]
            mode = min(live)[1] if live else HOST
        return mode if mode in MODES else HOST

    # -------------------------------------------------------- entry points
    def probe_multi(self, filts, meta, keys,
                    filts_host: Optional[np.ndarray] = None) -> np.ndarray:
        """Fused multi-table Bloom probe: (tables, keys) maybe-present
        matrix.  Host mode runs the vectorized numpy probe over
        ``filts_host`` (the filter stack's host mirror); kernel modes
        launch the Pallas probe over the device stack."""
        n_rows = int(filts.shape[0]) if filts is not None \
            else int(filts_host.shape[0])
        mode = self.decide("probe_multi", n_rows * len(keys))
        if mode == HOST and filts_host is not None:
            return bloom_probe_multi_host(filts_host, np.asarray(meta),
                                          np.asarray(keys, np.uint32))
        return bloom_probe_multi(filts, meta, keys,
                                 interpret=mode == INTERPRET)

    def _kernel_merge(self, runs, mode: str, drop_value: Optional[int]):
        return (*merge_dedup_kway(runs, block=self.merge_block,
                                  interpret=mode == INTERPRET,
                                  drop_value=drop_value), mode)

    @staticmethod
    def _host_merge(runs, drop_value: Optional[int]):
        mk, mv = merge_kway_host(runs)
        if drop_value is not None:
            mk, mv = drop_tombstones(mk, mv)
        return mk, mv

    def merge_kway(self, runs, drop_value: Optional[int] = None):
        """One-shot k-way newest-wins merge (newest run first).  Returns
        ``(keys_np, vals_np, mode)``, ``mode`` being the one that ran."""
        size = sum(len(k) for k, _ in runs)
        mode = self.decide("merge_kway", size)
        if mode == HOST:
            return (*self._host_merge(runs, drop_value), HOST)
        return self._kernel_merge(runs, mode, drop_value)

    def merge_kway_window(self, runs, starts, stops,
                          drop_value: Optional[int] = None):
        """Streaming-quantum window merge: merge only the
        ``[starts[i], stops[i])`` slice of each host run (the engine cuts
        at a global key boundary, so windows compose bit-identically).
        Returns ``(keys_np, vals_np, mode)`` like ``merge_kway``; an
        empty window keeps its position's age rank."""
        size = int(sum(e - s for s, e in zip(starts, stops)))
        mode = self.decide("merge_kway_window", size)
        windows = [(k[s:e], v[s:e])
                   for (k, v), s, e in zip(runs, starts, stops)]
        if mode != HOST:
            return self._kernel_merge(windows, mode, drop_value)
        windows = [w for w in windows if len(w[0])]
        if not windows:
            return np.empty(0, np.uint32), np.empty(0, np.int32), HOST
        if len(windows) == 1:
            wk, wv = windows[0]
            if drop_value is not None:
                wk, wv = drop_tombstones(wk, wv)
        else:
            wk, wv = self._host_merge(windows, drop_value)
        return np.ascontiguousarray(wk), np.ascontiguousarray(wv), HOST

    def scan_merge(self, runs,
                   drop_value: Optional[int] = None) -> tuple[np.ndarray,
                                                              np.ndarray]:
        """The read plane's k-way merge (range scans / fleet gathers):
        newest-wins merge with tombstone filtering fused, host results."""
        size = sum(len(k) for k, _ in runs)
        mode = self.decide("scan_merge", size)
        if mode == HOST:
            return self._host_merge(runs, drop_value)
        return self._kernel_merge(runs, mode, drop_value)[:2]

    # ------------------------------------------------------------- info
    def describe(self) -> dict:
        """Introspection for tests/benchmarks: forced modes, calibration
        presence, compiled availability."""
        return {
            "mode": self.mode,
            "forced": dict(self._forced),
            "calibrated": self.calibration is not None,
            "compiled_supported": compiled_supported(),
            "on_tpu": on_tpu(),
            "merge_block": self.merge_block,
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"ExecBackend({self.describe()!r})"
