"""Descriptor-keyed block-size autotuner for the fused conv backend.

The paper ties its throughput model to per-layer kernel timings measured
on the deployment target (§V-B); Synergy (1804.00706) and PICO
(2206.08662) likewise key per-layer execution choices off statically
available layer descriptors.  This module does the same for the Pallas
kernels: every conv layer's `ConvDescriptor` (equivalently its im2col
GEMM dims, Eq. 4) maps to a cache key; on first sight the tuner sweeps a
small (bm, bn, bk) candidate grid with best-of-k wall timing and persists
the winner to a JSON cache, so warmup cost is paid once per device kind.

Two kinds of measurement, both cached:

* ``tune(desc)`` — the block sweep for the Pallas fused kernel.  Only
  meaningful where that kernel actually executes (TPU, or forced
  interpret mode for CI validation); elsewhere the heuristic default
  blocks are recorded without timing (``swept=False``).
* ``measure_route(desc, fn, route)`` — best-of-k timing of the *serving
  route* the backend resolves to on this host (compiled fused kernel on
  TPU, fused XLA elsewhere), stored PER ROUTE so an "xla" measurement is
  never mistaken for a "pallas_fused" one.  These are the numbers
  `LayerTimePredictor` consumes as measured single-stream layer times,
  replacing the Eq. 5 regression prior for layers the tuner has seen
  (core/perfmodel.py).

Cache file format (``autotune_cache.json`` next to this module, override
with ``REPRO_AUTOTUNE_CACHE``), keyed by ``jax.devices()[0].device_kind``
so times measured on one device are never read on another::

    {"version": 1,
     "platforms": {
       "TPU v5 lite": {
         "conv_fused/f32/i14x14x256/f3x3/s1/p1/g1/ofm512": {
           "bm": 14, "bn": 128, "bk": 128,
           "time_s": 1.2e-4,     # best sweep candidate seconds
           "routes": {"pallas_fused": 9.8e-5},  # serving-route seconds
           "swept": true, "candidates": 9},
       ...}}}

Keys carry geometry, not layer names, so every VGG-16 3x3/512 conv at
14x14 shares one entry.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.descriptors import ConvDescriptor
from .config import default_interpret, on_tpu

_DEFAULT_CACHE = os.path.join(os.path.dirname(__file__), "autotune_cache.json")
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_SWEEP = "REPRO_AUTOTUNE_SWEEP"  # force the block sweep off-TPU (CI)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    bm: int
    bn: int
    bk: int

    def as_kwargs(self) -> Dict[str, int]:
        return {"block_m": self.bm, "block_n": self.bn, "block_k": self.bk}


def descriptor_key(desc: ConvDescriptor, op: str = "conv_fused") -> str:
    """Geometry-only cache key (layer-name independent)."""
    if desc.kind == "fc":
        return f"{op}/f32/fc/K{desc.i_w * desc.i_h * desc.i_d}/M{desc.ofm}"
    return (
        f"{op}/f32/i{desc.i_h}x{desc.i_w}x{desc.i_d}/f{desc.f_h}x{desc.f_w}"
        f"/s{desc.stride}/p{desc.pad}/g{desc.groups}/ofm{desc.ofm}"
    )


def candidate_blocks(
    ow: int, cout: int, cin: int, max_candidates: int = 12
) -> List[BlockConfig]:
    """(bm, bn, bk) sweep grid, deduped, every block tile-legal on the TPU.

    A block's last two dims must be multiples of (8, 128) or span the
    whole array dim, so ``bm`` is a multiple of 8 or the whole output
    row, and ``bn``/``bk`` are multiples of 128 or the whole
    ``cout``/``cin``.  Whole dims are offered only up to 256 output and
    512 input channels, which keeps the weight block within VMEM.  The
    half-row ``bm`` (rounded up to 8) gives narrow layers a second point
    to sweep.  The untuned heuristic (conv_fused.default_blocks) is
    always a candidate, so the tuned pick can never lose to it by
    construction."""
    from .conv_fused import _ceil_to, default_blocks

    bms = sorted({v for v in (32, 128, _ceil_to(-(-ow // 2), 8)) if v < ow} | {ow})
    bns = sorted({v for v in (128, 256) if v < cout} | ({cout} if cout <= 256 else set()))
    bks = sorted(({128} if cin > 128 else set()) | ({cin} if cin <= 512 else set()))
    dm, dn, dk = default_blocks(ow, cout, cin)
    # the heuristic lane-rounds above small dims; clamp so every candidate
    # respects the layer's dims (the kernel would clamp identically)
    default = BlockConfig(min(dm, ow), min(dn, cout), min(dk, cin))
    out, seen = [], set()
    for cand in [default] + [
        BlockConfig(bm, bn, bk) for bm in bms for bn in bns for bk in bks
    ]:
        if cand not in seen:
            seen.add(cand)
            out.append(cand)
    return out[:max_candidates]


def _best_of_k(fn: Callable[[], None], k: int) -> float:
    fn()  # compile / warm
    ts = []
    for _ in range(k):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(min(ts))


class ConvAutotuner:
    """Block-config + route-time cache for the fused conv backend.

    ``timings_run`` counts actual timing sweeps (not cache hits) — the
    round-trip tests assert it stays 0 on a warm cache.
    """

    def __init__(
        self,
        cache_path: Optional[str] = None,
        device_kind: Optional[str] = None,
        repeats: int = 3,
        sweep: Optional[bool] = None,
        proxy_rows: int = 4,
    ):
        import jax

        self.cache_path = cache_path or os.environ.get(_ENV_CACHE) or _DEFAULT_CACHE
        self.device_kind = device_kind or jax.devices()[0].device_kind
        self.repeats = repeats
        # sweep=None: sweep only where the Pallas kernel really executes
        # (TPU), or when CI forces it; the sweep in interpret mode is a
        # validation path, not a perf claim.
        if sweep is None:
            sweep = on_tpu() or os.environ.get(_ENV_SWEEP, "") not in ("", "0")
        self.sweep = sweep
        self.proxy_rows = proxy_rows
        self.timings_run = 0
        self._entries: Dict[str, dict] = {}
        self.load()

    # ------------------------------------------------------------ persistence
    #
    # The cache is an *accelerator*, never a correctness dependency: a
    # corrupted, truncated, or concurrently-rewritten file must degrade to
    # re-timing, not raise.  Multi-model co-serving makes this load-bearing
    # — several planners share one cache file, and two tuners (or two
    # processes) can race on it.
    @staticmethod
    def _read_cache(path: str) -> dict:
        """Best-effort parse of a cache file; {} on any damage."""
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        return data if isinstance(data, dict) else {}

    def load(self) -> None:
        """Adopt the file's entries for this device kind; tolerant of damage
        (missing file, invalid JSON, wrong schema) — a broken cache means
        an empty cache, and the tuner re-times on demand."""
        self._entries = {}
        platforms = self._read_cache(self.cache_path).get("platforms", {})
        if not isinstance(platforms, dict):
            return
        entries = platforms.get(self.device_kind, {})
        if not isinstance(entries, dict):
            return
        # drop individually-damaged entries (and damaged routes sub-dicts
        # inside otherwise-healthy entries), keep everything else
        for k, v in entries.items():
            if not isinstance(v, dict):
                continue
            if "routes" in v and not isinstance(v["routes"], dict):
                v = {kk: vv for kk, vv in v.items() if kk != "routes"}
            self._entries[k] = v

    def save(self) -> None:
        """Merge this tuner's entries into the file atomically.

        The re-read + ``os.replace`` of a writer-unique temp file makes a
        crashed or concurrent writer harmless: the final file is always
        one writer's complete, valid JSON (a lost update costs a re-time
        later, never a parse error).  A damaged existing file is simply
        rebuilt."""
        data = self._read_cache(self.cache_path)
        if not isinstance(data.get("platforms"), dict):
            data = {"version": 1, "platforms": {}}
        data.setdefault("version", 1)
        mine = data["platforms"].setdefault(self.device_kind, {})
        if not isinstance(mine, dict):
            mine = data["platforms"][self.device_kind] = {}
        for key, entry in self._entries.items():
            hit = mine.get(key)
            if isinstance(hit, dict):  # merge: keep a peer's routes/blocks
                merged = dict(hit)
                peer_routes = hit.get("routes")
                routes = {
                    **(peer_routes if isinstance(peer_routes, dict) else {}),
                    **entry.get("routes", {}),
                }
                merged.update(entry)
                if routes:
                    merged["routes"] = routes
                mine[key] = merged
            else:
                mine[key] = entry
        # unique temp name per writer: two concurrent save()s must never
        # interleave inside one temp file
        tmp = f"{self.cache_path}.{os.getpid()}.{id(self):x}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.cache_path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    # --------------------------------------------------------------- tuning
    def _sweep_shapes(self, desc: ConvDescriptor) -> Tuple[int, int, int, int]:
        """Spatially-capped proxy shape for interpret-mode sweeps: the
        kernel's per-output-row work is uniform, so ``proxy_rows`` rows
        time-extrapolate linearly while keeping interpret grids small."""
        if on_tpu():
            return desc.i_h, desc.i_w, desc.i_d, desc.ofm
        fh, s, p = desc.f_h, desc.stride, desc.pad
        h_for_rows = (self.proxy_rows - 1) * s + fh - 2 * p + s - 1
        h = max(fh, min(desc.i_h, h_for_rows))
        return h, desc.i_w, desc.i_d, desc.ofm

    def tune(self, desc: ConvDescriptor) -> BlockConfig:
        """Best (bm, bn, bk) for this descriptor, from cache or a sweep."""
        key = descriptor_key(desc)
        hit = self._entries.get(key)
        # route-only entries (measure_route) carry no block config — they
        # must not suppress the sweep
        if hit is not None and hit.get("bm"):
            return BlockConfig(hit["bm"], hit["bn"], hit["bk"])
        ow = desc.output_shape()[0]
        from .conv_fused import default_blocks, supports

        if (
            not self.sweep
            or desc.kind != "conv"
            or not supports(desc.f_h, desc.f_w, desc.stride, desc.groups)
        ):
            bm, bn, bk = default_blocks(ow, desc.ofm, desc.i_d)
            cfg = BlockConfig(bm, bn, bk)
            entry = self._entries.setdefault(key, {})
            entry.update(
                **dataclasses.asdict(cfg), time_s=None, swept=False, candidates=0
            )
            self.save()
            return cfg

        import jax.numpy as jnp

        from .conv_fused import conv2d_fused

        h, w_, c, ofm = self._sweep_shapes(desc)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((1, h, w_, c)), jnp.float32)
        wgt = jnp.asarray(
            rng.standard_normal((desc.f_h, desc.f_w, c, ofm)) * 0.05, jnp.float32
        )
        bias = jnp.zeros((ofm,), jnp.float32)
        best_cfg, best_t = None, float("inf")
        cands = candidate_blocks(ow, ofm, c)
        # every candidate is tile-legal, so one that fails to compile or
        # run is a kernel bug: it raises rather than leaving the sweep
        for cfg in cands:
            self.timings_run += 1
            try:
                t = _best_of_k(
                    lambda: conv2d_fused(
                        x, wgt, bias, stride=desc.stride, pad=desc.pad,
                        act="relu", **cfg.as_kwargs(),
                    ).block_until_ready(),
                    self.repeats,
                )
            except Exception as e:
                e.add_note(f"autotune {key}: sweep candidate {cfg}")
                raise
            if t < best_t:
                best_cfg, best_t = cfg, t
        entry = self._entries.setdefault(key, {})
        entry.update(
            **dataclasses.asdict(best_cfg),
            time_s=best_t, swept=True, candidates=len(cands),
        )
        self.save()
        return best_cfg

    # --------------------------------------------------- route measurement
    def measured_route(self, desc: ConvDescriptor, route: str) -> Optional[float]:
        hit = self._entries.get(descriptor_key(desc))
        if hit is None:
            return None
        return hit.get("routes", {}).get(route)

    def measure_route(
        self, desc: ConvDescriptor, fn: Callable[[], None], route: str = "default"
    ) -> float:
        """Best-of-k seconds of the layer's *serving route* (``fn`` runs
        one full layer), cached per ``route`` name — measurements from
        one backend are never served as another backend's times."""
        hit = self.measured_route(desc, route)
        if hit is not None:
            return hit
        self.timings_run += 1
        t = _best_of_k(fn, self.repeats)
        entry = self._entries.setdefault(
            descriptor_key(desc), {"swept": False, "candidates": 0}
        )
        entry.setdefault("routes", {})[route] = t
        self.save()
        return t

    def route_seconds(self, route: Optional[str] = None) -> Dict[str, float]:
        """{descriptor key: measured route seconds} — what the Eq. 5/8
        calibration layer consumes (LayerTimePredictor ``measured=``).
        ``route=None`` merges every route (single-backend sessions)."""
        out: Dict[str, float] = {}
        for k, v in self._entries.items():
            routes = v.get("routes", {})
            if route is not None:
                if route in routes:
                    out[k] = routes[route]
            elif routes:
                out[k] = min(routes.values())
        return out

    def entry(self, desc: ConvDescriptor) -> Optional[dict]:
        return self._entries.get(descriptor_key(desc))
