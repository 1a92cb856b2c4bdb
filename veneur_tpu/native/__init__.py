"""ctypes bindings for the native DogStatsD ingest engine (dogstatsd.cpp).

The .so is compiled on first import (g++ -O2, cached next to the source and
rebuilt when the source changes). `available()` gates the fast path: any
build/load failure falls back to the pure-Python parser with a warning —
semantics are identical (tests/test_native.py asserts parity).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import List, Optional

import numpy as np

log = logging.getLogger("veneur_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "dogstatsd.cpp")
_lib = None
_load_err: Optional[str] = None

# rings_inject verdicts (dogstatsd.cpp ring_push2): BACKPRESSURE means a
# full ring refused the datagram WITHOUT counting it — pace and retry;
# REJECTED means it was counted (toolong or admission shed) and is gone.
INJECT_OK = 1
INJECT_REJECTED = 0
INJECT_BACKPRESSURE = -1

# vr_stats / vrm_ring_stats, slot by slot: the ring, the packed emit, and
# what the thread that parses the ring spends (dogstatsd.cpp PumpCounters:
# blocked on an empty ring, the rest, and one datagram in 64 timed whole
# and in its key lookups, whose number and key-index entries read it
# counts)
RING_STATS = ("ring_depth", "ring_highwater", "pump_batches", "pump_stalls",
              "emit_packed_calls", "emit_packed_ns", "datagrams",
              "ring_dropped", "pump_wait_ns", "pump_busy_ns",
              "parse_sampled_ns", "parse_key_sampled_ns",
              "parse_sampled_datagrams", "key_lookups_sampled",
              "key_probes_sampled")


def _build_and_load():
    global _lib, _load_err
    if _lib is not None or _load_err is not None:
        return
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        # VENEUR_NATIVE_SANITIZE=1 builds with ASan+UBSan under a
        # distinct cache name so sanitized and plain processes never
        # race for the same .so. The loading process must arrange for
        # libasan to be resolvable (LD_PRELOAD under a non-instrumented
        # python) — see tests/test_native_sanitize.py.
        sanitize = os.environ.get("VENEUR_NATIVE_SANITIZE") == "1"
        prefix = "_dogstatsd_san_" if sanitize else "_dogstatsd_"
        so_path = os.path.join(_DIR, f"{prefix}{digest}.so")
        if not os.path.exists(so_path):
            for stale in os.listdir(_DIR):
                if (stale.startswith(prefix)
                        and stale.endswith(".so")
                        and stale != os.path.basename(so_path)):
                    try:
                        os.unlink(os.path.join(_DIR, stale))
                    except OSError:
                        pass
            # temp + rename so a concurrent process never dlopens a
            # half-written ELF
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                   "-pthread"]
            if sanitize:
                cmd += ["-g", "-fsanitize=address,undefined",
                        "-fno-sanitize-recover=all",
                        "-fno-omit-frame-pointer"]
            subprocess.run(cmd + ["-o", tmp_path, _SRC],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp_path, so_path)
        lib = ctypes.CDLL(so_path)
        lib.vt_new.restype = ctypes.c_void_p
        lib.vt_new.argtypes = [ctypes.c_uint32] * 5 + [ctypes.c_int] + \
            [ctypes.c_uint32] * 4
        lib.vt_free.argtypes = [ctypes.c_void_p]
        lib.vt_feed.restype = ctypes.c_int
        lib.vt_feed.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_int)]
        lib.vt_emit.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_void_p] * 10 + [ctypes.POINTER(ctypes.c_uint32)]
        lib.vt_emit_packed.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.vt_pending.restype = ctypes.c_int
        lib.vt_pending.argtypes = [ctypes.c_void_p]
        lib.vt_new_keys.restype = ctypes.c_int
        lib.vt_new_keys.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int]
        lib.vt_next_special.restype = ctypes.c_int
        lib.vt_next_special.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int]
        lib.vt_slot_for.restype = ctypes.c_int32
        lib.vt_slot_for.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_int)]
        lib.vt_sampled_directly.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.vt_live_keys.restype = ctypes.c_int
        lib.vt_live_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
        lib.vt_key_counters.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint64)]
        lib.vt_reset.argtypes = [ctypes.c_void_p]
        lib.vt_shard_map_set.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.vt_capacity_set.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_uint32] * 4
        lib.vt_table_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64)]
        lib.vt_stats.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.vr_start.restype = ctypes.c_void_p
        lib.vr_start.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_int),
                                 ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.vr_pump.restype = ctypes.c_int
        lib.vr_pump.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_uint64)]
        lib.vr_counters.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint64)]
        lib.vr_stats.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.vr_admission_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_int]
        lib.vr_admission_counters.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.vr_stop.argtypes = [ctypes.c_void_p]
        lib.vt_hash64_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.vi_import.restype = ctypes.c_int
        lib.vi_import.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.vi_stats.restype = ctypes.c_int
        lib.vi_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int]
        lib.vt_route_digest.restype = ctypes.c_uint32
        lib.vt_route_digest.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int]
        lib.vt_emit_sharded.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_void_p] * 10 + [ctypes.POINTER(ctypes.c_int32),
                                      ctypes.POINTER(ctypes.c_uint32)]
        lib.vrm_start.restype = ctypes.c_void_p
        lib.vrm_start.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
        lib.vrm_n_rings.restype = ctypes.c_int
        lib.vrm_n_rings.argtypes = [ctypes.c_void_p]
        lib.vrm_inject.restype = ctypes.c_int
        lib.vrm_inject.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                   ctypes.c_char_p, ctypes.c_int]
        lib.vrm_wait.restype = ctypes.c_int
        lib.vrm_wait.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.vrm_pending.restype = ctypes.c_int
        lib.vrm_pending.argtypes = [ctypes.c_void_p]
        lib.vrm_emit.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.vrm_emit_sharded.argtypes = [ctypes.c_void_p, ctypes.c_int] + \
            [ctypes.c_void_p] * 10 + [ctypes.POINTER(ctypes.c_int32),
                                      ctypes.POINTER(ctypes.c_uint32)]
        lib.vrm_pause.argtypes = [ctypes.c_void_p]
        lib.vrm_resume.argtypes = [ctypes.c_void_p]
        lib.vrm_reset.argtypes = [ctypes.c_void_p]
        lib.vrm_shard_map_set.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.vrm_capacity_set.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_uint32] * 4
        lib.vrm_table_stats.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint64)]
        lib.vrm_counters.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.POINTER(ctypes.c_uint64)]
        lib.vrm_ring_stats.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.POINTER(ctypes.c_uint64)]
        lib.vrm_admission_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_int]
        lib.vrm_admission_counters.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_uint64)]
        lib.vrm_stats.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint64)]
        lib.vrm_stop.argtypes = [ctypes.c_void_p]
        lib.vt_tenant_config.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_double, ctypes.c_uint32, ctypes.c_double,
            ctypes.c_double]
        lib.vt_tenant_params.argtypes = [
            ctypes.c_void_p, ctypes.c_double, ctypes.c_char_p, ctypes.c_int]
        lib.vt_tenant_names.restype = ctypes.c_int
        lib.vt_tenant_names.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int]
        lib.vt_tenant_table.restype = ctypes.c_int
        lib.vt_tenant_table.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int]
        lib.vt_tenant_restore.restype = ctypes.c_int
        lib.vt_tenant_restore.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_int]
        lib.vt_set_tenant.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int]
        lib.vt_tenant_rows.restype = ctypes.c_int
        lib.vt_tenant_rows.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        lib.vt_tenant_extract.restype = ctypes.c_int
        lib.vt_tenant_extract.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int]
        lib.vrm_tenant_counters.restype = ctypes.c_int
        lib.vrm_tenant_counters.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        _lib = lib
    except Exception as e:  # noqa: BLE001 — any failure => python fallback
        _load_err = str(e)
        log.warning("native ingest unavailable, using python parser: %s", e)


def available() -> bool:
    _build_and_load()
    return _lib is not None


KIND_NAMES = {0: "counter", 1: "gauge", 2: "histogram", 3: "set",
              4: "timer"}
KIND_IDS = {v: k for k, v in KIND_NAMES.items()}
# the engine's four key tables, in vt_live_keys / vt_table_stats order
LIVE_TABLES = ("counter", "gauge", "set", "histo")
# beside a scope in one byte: the key's slot came by the import path
IMPORTED_BIT = 0x80


def hash64_batch(members: List[bytes]) -> "np.ndarray":
    """FNV-1a 64 of each byte string, hashed in one C call (bit-identical
    to utils.hashing.fnv1a_64). Raises when the engine isn't built —
    callers gate on available()."""
    _build_and_load()
    if _lib is None:
        raise RuntimeError(f"native ingest unavailable: {_load_err}")
    n = len(members)
    buf = b"".join(members)
    offs = np.zeros(n + 1, np.int64)
    if n:
        np.cumsum([len(m) for m in members], out=offs[1:])
    out = np.empty(n, np.uint64)
    _lib.vt_hash64_batch(
        buf, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return out


def tenant_extract(tag: str, data: bytes) -> Optional[str]:
    """The C++ engine's tenant-tag extraction (vt_tenant_extract) exposed
    standalone: the value of the first well-formed `tag` occurrence in the
    raw datagram, or None for every default-tenant outcome (missing tag,
    empty/oversized/invalid-UTF-8 value, tag split by truncation). Tests
    fuzz this against reliability/tenancy.py extract_tenant for parity.
    Raises when the engine isn't built — callers gate on available()."""
    _build_and_load()
    if _lib is None:
        raise RuntimeError(f"native ingest unavailable: {_load_err}")
    tag_b = tag.encode("utf-8", "surrogateescape")
    out = ctypes.create_string_buffer(256)
    n = _lib.vt_tenant_extract(tag_b, len(tag_b), data, len(data), out,
                               len(out))
    if n <= 0:
        return None
    return out.raw[:n].decode("utf-8", "surrogateescape")


def route_digest(kind: str, name: str, joined_tags: str) -> int:
    """The C++ engine's routing digest (fnv1a-32 over name, kind, joined
    tags) — must be byte-identical to collective.keytable.route_digest;
    tests/test_native.py pins the parity over a fuzz corpus. Raises when
    the engine isn't built — callers gate on available()."""
    _build_and_load()
    if _lib is None:
        raise RuntimeError(f"native ingest unavailable: {_load_err}")
    name_b = name.encode("utf-8", "surrogateescape")
    kind_b = kind.encode("utf-8")
    tags_b = joined_tags.encode("utf-8", "surrogateescape")
    return int(_lib.vt_route_digest(name_b, len(name_b), kind_b,
                                    len(kind_b), tags_b, len(tags_b)))


def _tenant_merge(acc: dict, one: dict) -> None:
    """Accumulate one ring's per-tenant drain into a host-wide fold
    (ring_tenant_drain_one layout: nested admitted/shed class dicts plus
    a demoted_rows scalar)."""
    for tenant, ent in one.items():
        dst = acc.setdefault(tenant, {})
        for side in ("admitted", "shed"):
            for cls, n in ent.get(side, {}).items():
                d = dst.setdefault(side, {})
                d[cls] = d.get(cls, 0) + n
        if ent.get("demoted_rows"):
            dst["demoted_rows"] = (dst.get("demoted_rows", 0)
                                   + ent["demoted_rows"])


class NativeIngest:
    """One parser+keytable+stager instance (mirrors aggregation/host.py
    KeyTable+Batcher, but in C++)."""

    def __init__(self, spec, bspec, n_shards: int = 1):
        _build_and_load()
        if _lib is None:
            raise RuntimeError(f"native ingest unavailable: {_load_err}")
        self.spec = spec
        self.bspec = bspec
        self._h = _lib.vt_new(
            spec.counter_capacity, spec.gauge_capacity, spec.set_capacity,
            spec.histo_capacity, n_shards, spec.hll_precision,
            bspec.counter, bspec.gauge, bspec.set, bspec.histo)
        self._keybuf = ctypes.create_string_buffer(1 << 20)
        self._specialbuf = ctypes.create_string_buffer(1 << 16)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h and _lib is not None:
            _lib.vt_free(h)
            self._h = None

    def feed(self, data: bytes, start: int = 0) -> tuple:
        """Parse a packet buffer from byte offset `start`. Returns
        (full, consumed): full means a staging area filled and emit()
        should run; consumed is the absolute offset of the first
        unhandled byte — resume with feed(data, consumed) after emitting.
        The same bytes object is passed back unsliced, so a lane-full
        stop never copies a multi-KB remainder (same offset model as
        import_metriclist)."""
        consumed = ctypes.c_int(0)
        rc = _lib.vt_feed(self._h, data, len(data), start,
                          ctypes.byref(consumed))
        return bool(rc), consumed.value

    def emit_into(self, batcher_arrays) -> tuple:
        """Copy staged samples into numpy arrays. batcher_arrays is the
        tuple (c_slot, c_inc, g_slot, g_val, s_slot, s_reg, s_rho, h_slot,
        h_val, h_wt) of pre-sentinel-filled numpy arrays."""
        counts = (ctypes.c_uint32 * 4)()
        ptrs = [a.ctypes.data_as(ctypes.c_void_p) for a in batcher_arrays]
        _lib.vt_emit(self._h, *ptrs, counts)
        return tuple(counts)

    def emit_packed(self, flat: "np.ndarray", lane_offs: "np.ndarray",
                    prev_counts: "np.ndarray") -> tuple:
        """Zero-copy emit into a caller-owned flat i32 buffer laid out
        exactly like aggregation/step.py pack_batch. `lane_offs` is the
        int32[10] word offsets of the ten native lanes in that layout;
        `prev_counts` is this buffer's uint32[4] counts from ITS previous
        emit (updated in place — the engine re-sentinels only the rows the
        previous emit dirtied past the new counts). Returns (nc, ng, ns,
        nh) and resets staging."""
        counts = (ctypes.c_uint32 * 4)()
        _lib.vt_emit_packed(
            self._h,
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lane_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            prev_counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            counts)
        return tuple(counts)

    def emit_sharded(self, batcher_arrays, bounds: "np.ndarray") -> tuple:
        """Pre-sharded emit: like emit_into but rows arrive grouped by
        owner shard (stable, so arrival order — gauge LWW — is preserved
        within each shard) with slots rebased shard-local. `bounds`
        (int32[4*(n_shards+1)], kinds in counter/gauge/set/histo order)
        receives per-kind shard prefix bounds so per-shard batchers take
        contiguous slices with no argsort. Returns (nc, ng, ns, nh)."""
        counts = (ctypes.c_uint32 * 4)()
        ptrs = [a.ctypes.data_as(ctypes.c_void_p) for a in batcher_arrays]
        _lib.vt_emit_sharded(
            self._h, *ptrs,
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), counts)
        return tuple(counts)

    def pending(self) -> int:
        return _lib.vt_pending(self._h)

    def slot_for(self, kind: str, name: str, joined_tags: str, scope: int,
                 digest: int, imported: bool = False):
        """(slot, was_new) for a Python-side caller sharing the native slot
        space; slot is None at capacity. `imported` marks a caller on the
        import path: it is what the interval's first arrival of the key
        records beside its scope (live_keys)."""
        was_new = ctypes.c_int(0)
        name_b = name.encode("utf-8", "surrogateescape")
        tags_b = joined_tags.encode("utf-8", "surrogateescape")
        slot = _lib.vt_slot_for(
            self._h, KIND_IDS[kind], scope | (IMPORTED_BIT if imported else 0),
            name_b, len(name_b), tags_b, len(tags_b), digest & 0xFFFFFFFF,
            ctypes.byref(was_new))
        return (None if slot < 0 else slot), bool(was_new.value)

    def sampled_directly(self, slot: int) -> None:
        """A histo slot took a directly-sampled value: it stops being
        imported-only in this interval."""
        _lib.vt_sampled_directly(self._h, slot)

    def live_keys(self, table: str) -> tuple:
        """(slots int32[n], first uint8[n]) of one table (LIVE_TABLES) in
        this interval so far: the slots its keys were touched at, in
        first-arrival order, and a row each the scope of the interval's
        first arrival, IMPORTED_BIT set where that came by the import
        path. Keys outlive the interval (dogstatsd.cpp KindTable); this
        list is what an interval owns."""
        t = LIVE_TABLES.index(table)
        cap = 0
        while True:
            slots = np.empty(cap, np.int32)
            first = np.empty(cap, np.uint8)
            n = _lib.vt_live_keys(
                self._h, t,
                slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                first.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap)
            if n >= 0:
                return slots[:n], first[:n]
            # the count, then the copy; ring workers may add between the two
            cap = -n + 1024

    def key_counters(self) -> dict:
        """What the intervals closed so far (reset()) held, over the four
        tables: keys_live, of them keys_new (allocated in their
        interval), and the keys_evicted for them."""
        s = (ctypes.c_uint64 * 3)()
        _lib.vt_key_counters(self._h, s)
        return {"keys_live": s[0], "keys_new": s[1], "keys_evicted": s[2]}

    def drain_new_keys(self) -> List[tuple]:
        """[(kind, slot, scope, name, joined_tags, imported)] allocated
        since the last drain: a key's first allocation, or its next after
        an eviction. The scope byte's bit 7 marks slots first created by
        the native import path (imported_only labeling)."""
        n = _lib.vt_new_keys(self._h, self._keybuf,
                             len(self._keybuf))
        if n < 0:
            self._keybuf = ctypes.create_string_buffer(-n * 2)
            n = _lib.vt_new_keys(self._h, self._keybuf, len(self._keybuf))
        out = []
        raw = ctypes.string_at(self._keybuf, n)
        off = 0
        while off < n:
            kind = raw[off]
            slot = int.from_bytes(raw[off + 1:off + 5], "little",
                                  signed=True)
            scope = raw[off + 5] & 0x7F
            imported = bool(raw[off + 5] & 0x80)
            nl = int.from_bytes(raw[off + 6:off + 8], "little")
            name = raw[off + 8:off + 8 + nl].decode(
                "utf-8", "surrogateescape")
            off += 8 + nl
            tl = int.from_bytes(raw[off:off + 2], "little")
            tags = raw[off + 2:off + 2 + tl].decode(
                "utf-8", "surrogateescape")
            off += 2 + tl
            out.append((KIND_NAMES[kind], slot, scope, name, tags,
                        imported))
        return out

    def import_metriclist(self, data: bytes, offset: int = 0):
        """Decode + stage a serialized forwardrpc.MetricList starting at
        `offset` (the whole buffer is passed zero-copy; re-entry never
        re-slices a multi-MB remainder). Returns
        (handled_count, consumed_abs, fallback_spans, lane_full) —
        consumed_abs is the absolute offset fully handled (re-enter
        there after emitting when lane_full), fallback_spans is
        [(abs_off, length)] of Metric submessages for the Python path."""
        consumed = ctypes.c_int(0)
        n_fb = ctypes.c_int(0)
        full_stop = ctypes.c_int(0)
        fb_cap = 1024
        fb_off = (ctypes.c_int32 * fb_cap)()
        fb_len = (ctypes.c_int32 * fb_cap)()
        staged = _lib.vi_import(self._h, data, len(data), offset,
                                ctypes.byref(consumed), fb_off, fb_len,
                                fb_cap, ctypes.byref(n_fb),
                                ctypes.byref(full_stop))
        spans = [(fb_off[i], fb_len[i]) for i in range(n_fb.value)]
        return (staged, consumed.value, spans, bool(full_stop.value))

    def drain_import_stats(self):
        """(slots, mins, maxes, recip_corrs) numpy arrays of the
        per-imported-histogram scalar stats staged by import_metriclist."""
        cap = 4096
        slots = np.empty(cap, np.int32)
        mns = np.empty(cap, np.float32)
        mxs = np.empty(cap, np.float32)
        rc = np.empty(cap, np.float32)
        out = [[], [], [], []]
        while True:
            n = _lib.vi_stats(
                self._h,
                slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                mns.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                mxs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                rc.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), cap)
            if n <= 0:
                break
            out[0].append(slots[:n].copy())
            out[1].append(mns[:n].copy())
            out[2].append(mxs[:n].copy())
            out[3].append(rc[:n].copy())
            if n < cap:
                break
        if not out[0]:
            z = np.empty(0, np.float32)
            return np.empty(0, np.int32), z, z, z
        return tuple(np.concatenate(x) for x in out)

    def drain_specials(self) -> List[bytes]:
        """Event/service-check lines the C++ parser escalated."""
        out = []
        while True:
            n = _lib.vt_next_special(self._h, self._specialbuf,
                                     len(self._specialbuf))
            if n == 0:
                break
            if n < 0:
                self._specialbuf = ctypes.create_string_buffer(-n * 2)
                continue
            out.append(self._specialbuf.raw[:n])
        return out

    def reset(self):
        """Flush boundary: the next interval's live lists start empty; the
        keys and their slots stay (a staged shard map or capacity empties
        the tables instead)."""
        r = getattr(self, "_rings", None)
        if r:
            # the master tables AND every ring's key-replica cache;
            # callers hold the rings_pause() quiesce across this
            _lib.vrm_reset(r)
        else:
            _lib.vt_reset(self._h)

    def shard_map_set(self, n_shards: int):
        """Stage a shard-map change; it takes effect at the next reset()
        (i.e. inside the swap quiesce), never immediately. Only
        veneur_tpu/reshard/quiesce.py may call this — vtlint's
        reshard-quiesce pass enforces the boundary."""
        r = getattr(self, "_rings", None)
        if r:
            _lib.vrm_shard_map_set(r, int(n_shards))
        else:
            _lib.vt_shard_map_set(self._h, int(n_shards))

    def capacity_set(self, counter: int, gauge: int, set_: int,
                     histo: int):
        """Stage new per-kind table capacities (0 = keep current); they
        take effect at the next reset() (i.e. inside the swap quiesce),
        never immediately. Only veneur_tpu/tables/growth.py may call
        this — vtlint's table-grow-quiesce pass enforces the boundary."""
        r = getattr(self, "_rings", None)
        if r:
            _lib.vrm_capacity_set(r, int(counter), int(gauge), int(set_),
                                  int(histo))
        else:
            _lib.vt_capacity_set(self._h, int(counter), int(gauge),
                                 int(set_), int(histo))

    def table_stats(self) -> dict:
        """Per-kind key-table occupancy for the growth planner:
        {kind: (keys live in this interval, dropped, capacity)} over the
        engine's four tables. Locks the key tables shared — safe alongside ring
        parsing."""
        s = (ctypes.c_uint64 * 12)()
        r = getattr(self, "_rings", None)
        if r:
            _lib.vrm_table_stats(r, s)
        else:
            _lib.vt_table_stats(self._h, s)
        return {k: (int(s[i * 3]), int(s[i * 3 + 1]), int(s[i * 3 + 2]))
                for i, k in enumerate(LIVE_TABLES)}

    def stats(self) -> dict:
        s = (ctypes.c_uint64 * 3)()
        r = getattr(self, "_rings", None)
        if r:
            _lib.vrm_stats(r, s)  # summed over ring parsers + master
        else:
            _lib.vt_stats(self._h, s)
        return {"processed": s[0], "parse_errors": s[1], "dropped": s[2]}

    # -- native UDP reader group (vr_* in dogstatsd.cpp) --------------------

    def readers_start(self, fds: List[int], max_len: int = 65536,
                      ring_cap: int = 65536) -> None:
        """Spawn one C++ recvmmsg thread per fd, feeding the shared
        datagram ring drained by pump(). Each fd is dup()ed into C++
        ownership (vr_start), so the Python sockets may be closed at any
        time after this returns; the dups are released by
        readers_stop()."""
        arr = (ctypes.c_int * len(fds))(*fds)
        self._readers = _lib.vr_start(self._h, arr, len(fds), max_len,
                                      ring_cap)

    def pump(self, max_wait_ms: int) -> tuple:
        """Drain queued datagrams into staging (blocks in C++ with the GIL
        released while the ring is idle). Returns (full, stats) where full
        means a staging lane filled — emit and call pump(0) again — and
        stats is {parsed, ring_depth, ring_dropped, datagrams}."""
        out = (ctypes.c_uint64 * 4)()
        full = _lib.vr_pump(self._readers, max_wait_ms, out)
        return bool(full), {"parsed": out[0], "ring_depth": out[1],
                            "ring_dropped": out[2], "datagrams": out[3]}

    def reader_counters(self) -> dict:
        """Live reader counters, callable from any thread. With the
        multi-ring engine the totals are exact sums over every ring."""
        m = getattr(self, "_rings", None)
        if m:
            agg = {"datagrams": 0, "ring_dropped": 0, "ring_depth": 0,
                   "toolong": 0}
            out = (ctypes.c_uint64 * 4)()
            for i in range(self._n_rings):
                _lib.vrm_counters(m, i, out)
                agg["datagrams"] += out[0]
                agg["ring_dropped"] += out[1]
                agg["ring_depth"] += out[2]
                agg["toolong"] += out[3]
            return agg
        r = getattr(self, "_readers", None)
        if not r:
            return {"datagrams": 0, "ring_dropped": 0, "ring_depth": 0,
                    "toolong": 0}
        out = (ctypes.c_uint64 * 4)()
        _lib.vr_counters(r, out)
        return {"datagrams": out[0], "ring_dropped": out[1],
                "ring_depth": out[2], "toolong": out[3]}

    def ring_stats(self) -> dict:
        """Deep ring/emit telemetry snapshot, callable from any thread
        (one C++ lock, no hot-path cost), keyed as `RING_STATS`: ring
        depth + high-water, pump batch/stall counts, emit_packed call/ns
        totals, datagram and ring-drop totals, and the parsing thread's
        wait, busy and sampled parse time, with the sampled key lookups
        and the key-index entries they read. Zeros when no reader group is
        running. With the multi-ring engine, counters are exact
        cross-ring sums and ring_highwater is the per-ring max."""
        if getattr(self, "_rings", None):
            agg = dict.fromkeys(RING_STATS, 0)
            for per in self.ring_stats_per_ring():
                for k in RING_STATS:
                    agg[k] = (max(agg[k], per[k]) if k == "ring_highwater"
                              else agg[k] + per[k])
            return agg
        r = getattr(self, "_readers", None)
        if not r:
            return dict.fromkeys(RING_STATS, 0)
        out = (ctypes.c_uint64 * len(RING_STATS))()
        _lib.vr_stats(r, out)
        return dict(zip(RING_STATS, out))

    def admission_set(self, enabled: bool, state: int, rate: float,
                      burst: float, high_tags) -> None:
        """Push the OverloadController's statsd admission knobs into the
        reader ring (called from the controller poll thread). high_tags is
        an iterable of shed_priority_tags strings. With the multi-ring
        engine, rate/burst split evenly across rings inside the C++ so the
        host-level admit rate matches the single-ring contract."""
        joined = "\n".join(high_tags).encode("utf-8", "surrogateescape")
        m = getattr(self, "_rings", None)
        if m:
            _lib.vrm_admission_set(m, 1 if enabled else 0, int(state),
                                   float(rate), float(burst), joined,
                                   len(joined))
            return
        r = getattr(self, "_readers", None)
        if not r:
            return
        _lib.vr_admission_set(r, 1 if enabled else 0, int(state),
                              float(rate), float(burst), joined,
                              len(joined))

    def admission_drain(self) -> dict:
        """Drain-and-reset exact per-class ring admission deltas:
        {"admitted": {class: n}, "shed": {class: n}} with zero entries
        omitted (classes: self/high/low, mirroring PriorityClassifier).
        With the multi-ring engine, the per-class deltas are drained from
        EVERY ring and summed so the invariant sent == toolong + admitted
        + shed holds host-wide."""
        names = ("self", "high", "low")
        m = getattr(self, "_rings", None)
        if m:
            adm = [0, 0, 0]
            shed = [0, 0, 0]
            tenants: dict = {}
            for i in range(self._n_rings):
                one = self.ring_admission_drain_one(i)
                for c in range(3):
                    adm[c] += one["admitted"].get(names[c], 0)
                    shed[c] += one["shed"].get(names[c], 0)
                _tenant_merge(tenants, one.get("tenants", {}))
            d = {
                "admitted": {names[i]: adm[i] for i in range(3) if adm[i]},
                "shed": {names[i]: shed[i] for i in range(3) if shed[i]},
            }
            if tenants:
                d["tenants"] = tenants
            return d
        r = getattr(self, "_readers", None)
        if not r:
            return {"admitted": {}, "shed": {}}
        out = (ctypes.c_uint64 * 6)()
        _lib.vr_admission_counters(r, out)
        return {
            "admitted": {names[i]: out[i] for i in range(3) if out[i]},
            "shed": {names[i]: out[3 + i] for i in range(3) if out[3 + i]},
        }

    def readers_stop(self) -> None:
        r = getattr(self, "_readers", None)
        if r:
            _lib.vr_stop(r)
            self._readers = None
        m = getattr(self, "_rings", None)
        if m:
            _lib.vrm_stop(m)
            self._rings = None
            self._n_rings = 0

    # -- multi-ring engine (vrm_* in dogstatsd.cpp) -------------------------

    @property
    def n_rings(self) -> int:
        """Rings in the multi-ring engine; 0 when it isn't running."""
        return getattr(self, "_n_rings", 0) if getattr(
            self, "_rings", None) else 0

    def rings_start(self, n_rings: int, fds=None, max_len: int = 65536,
                    ring_cap: int = 65536, pin_cores=None) -> None:
        """Start the multi-ring engine: one ring + parser thread pair per
        entry (vrm_start), all sharing this instance's key tables. fds[i]
        >= 0 attaches a dup()ed SO_REUSEPORT socket to ring i; None/-1
        entries make inject-only rings (benches, tests use rings_inject
        for deterministic placement). pin_cores[i] >= 0 pins ring i's
        reader+worker threads to that core."""
        fd_arr = (ctypes.c_int * n_rings)(
            *[(fds[i] if fds is not None and i < len(fds)
               and fds[i] is not None else -1) for i in range(n_rings)])
        pin_arr = None
        if pin_cores:
            pin_arr = (ctypes.c_int * n_rings)(
                *[(pin_cores[i] if i < len(pin_cores) else -1)
                  for i in range(n_rings)])
        self._rings = _lib.vrm_start(self._h, fd_arr, n_rings, max_len,
                                     ring_cap, pin_arr)
        self._n_rings = n_rings

    def rings_inject(self, ring: int, data: bytes) -> int:
        """Queue one datagram onto ring i through the same toolong/
        admission/ring-cap accounting as the socket path. Returns a
        verdict: INJECT_OK (1) queued; INJECT_REJECTED (0) counted and
        dropped (toolong or admission shed — the datagrams == toolong +
        admitted + shed identity holds); INJECT_BACKPRESSURE (-1) the
        ring is full and NOTHING was counted — the caller still owns the
        datagram and should pace, then retry. Retrying a BACKPRESSURE
        verdict never double-counts (the old bool return counted the
        datagram before the ring-full check, so pace-and-retry loops
        inflated the received count)."""
        return int(_lib.vrm_inject(self._rings, ring, data, len(data)))

    def rings_wait(self, max_wait_ms: int) -> int:
        """Block (GIL released) until a ring stalls on full staging or
        staging runs rich, or the timeout passes. Returns the number of
        stalled rings."""
        return _lib.vrm_wait(self._rings, max_wait_ms)

    def rings_pending(self) -> int:
        """Staged rows across all rings (racy snapshot, idle heuristic)."""
        return _lib.vrm_pending(self._rings)

    def rings_emit(self, ring: int, flat: "np.ndarray",
                   lane_offs: "np.ndarray",
                   prev_counts: "np.ndarray") -> tuple:
        """emit_packed for ring i's staging into its packed arena row
        (same layout/sentinel contract as emit_packed; `flat` is the
        ring's row view of the (rings, words) arena)."""
        counts = (ctypes.c_uint32 * 4)()
        _lib.vrm_emit(
            self._rings, ring,
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lane_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            prev_counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            counts)
        return tuple(counts)

    def rings_emit_sharded(self, ring: int, batcher_arrays,
                           bounds: "np.ndarray") -> tuple:
        """emit_sharded for ring i's staging: rows grouped by owner shard
        with shard-local slots and per-kind shard bounds — the sharded
        backend's per-ring drain."""
        counts = (ctypes.c_uint32 * 4)()
        ptrs = [a.ctypes.data_as(ctypes.c_void_p) for a in batcher_arrays]
        _lib.vrm_emit_sharded(
            self._rings, ring, *ptrs,
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), counts)
        return tuple(counts)

    def rings_pause(self) -> None:
        """Swap-boundary quiesce: no ring worker parses again until
        rings_resume(). Emit every ring, then reset(), inside this."""
        _lib.vrm_pause(self._rings)

    def rings_resume(self) -> None:
        _lib.vrm_resume(self._rings)

    def ring_counters_one(self, ring: int) -> dict:
        """Per-ring reader counters (reader_counters layout)."""
        out = (ctypes.c_uint64 * 4)()
        _lib.vrm_counters(self._rings, ring, out)
        return {"datagrams": out[0], "ring_dropped": out[1],
                "ring_depth": out[2], "toolong": out[3]}

    def ring_stats_one(self, ring: int) -> dict:
        """Per-ring deep telemetry (ring_stats layout)."""
        out = (ctypes.c_uint64 * len(RING_STATS))()
        _lib.vrm_ring_stats(self._rings, ring, out)
        return dict(zip(RING_STATS, out))

    def ring_stats_per_ring(self) -> List[dict]:
        """ring_stats_one for every ring (empty when not multi-ring)."""
        if not getattr(self, "_rings", None):
            return []
        out = []
        for i in range(self._n_rings):
            out.append(self.ring_stats_one(i))
        return out

    def ring_admission_drain_one(self, ring: int) -> dict:
        """Drain-and-reset ring i's exact per-class admission deltas
        (admission_drain layout), plus — when the tenant table is live —
        a "tenants" sub-dict of per-tenant admitted/shed/demoted_rows
        deltas drained through the SAME per-ring fold point. Callers must
        fold across ALL rings — use admission_drain() for the exact
        host-wide sum."""
        out = (ctypes.c_uint64 * 6)()
        _lib.vrm_admission_counters(self._rings, ring, out)
        names = ("self", "high", "low")
        d = {
            "admitted": {names[i]: out[i] for i in range(3) if out[i]},
            "shed": {names[i]: out[3 + i] for i in range(3) if out[3 + i]},
        }
        if getattr(self, "_tenant_names", None) is not None:
            tenants = self.ring_tenant_drain_one(ring)
            if tenants:
                d["tenants"] = tenants
        return d

    # -- multi-tenant identity / fairness / quarantine ----------------------

    def tenant_config(self, enabled: bool, tag: str = "tenant:",
                      burst_mult: float = 2.0, q_max_keys: int = 0,
                      q_decay: float = 0.5,
                      q_readmit_frac: float = 0.5) -> None:
        """Create/configure the tenant table on the master parser. Must
        run before rings_start — the tag is read lock-free on the
        admission path. Interns the default tenant as id 0."""
        tag_b = tag.encode("utf-8", "surrogateescape")
        _lib.vt_tenant_config(self._h, 1 if enabled else 0, tag_b,
                              len(tag_b), float(burst_mult),
                              int(q_max_keys), float(q_decay),
                              float(q_readmit_frac))
        if getattr(self, "_tenant_names", None) is None:
            self._tenant_names = {0: "default"}

    def tenant_params(self, base_rate: float, weights: dict) -> None:
        """Per-poll push: base admit rate (tokens/s per unit weight; <=0
        disables the fairness buckets) and {tenant: weight} overrides.
        Unknown names are interned so weights precede first traffic."""
        blob = "".join(
            f"{name}\t{float(w)}\n" for name, w in weights.items()
        ).encode("utf-8", "surrogateescape")
        _lib.vt_tenant_params(self._h, float(base_rate), blob, len(blob))

    def _tenant_refresh_names(self) -> None:
        """Drain newly interned (id, name) pairs into the local map."""
        cap = 4096
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = _lib.vt_tenant_names(self._h, buf, cap)
            if n >= 0:
                break
            cap = -n * 2
        raw = buf.raw
        off = 0
        for _ in range(n):
            tid = int.from_bytes(raw[off:off + 4], "little", signed=True)
            ln = int.from_bytes(raw[off + 4:off + 6], "little")
            self._tenant_names[tid] = raw[off + 6:off + 6 + ln].decode(
                "utf-8", "surrogateescape")
            off += 6 + ln

    def _tenant_name(self, tid: int) -> str:
        name = self._tenant_names.get(tid)
        if name is None:
            self._tenant_refresh_names()
            name = self._tenant_names.get(tid, f"tenant#{tid}")
        return name

    def tenant_table(self) -> dict:
        """Non-destructive snapshot of every interned tenant:
        {name: {"demoted": bool, "key_est": float}} (checkpoint +
        quarantine telemetry source)."""
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = _lib.vt_tenant_table(self._h, buf, cap)
            if n >= 0:
                break
            cap = -n * 2
        raw = buf.raw
        out = {}
        off = 0
        for _ in range(n):
            tid = int.from_bytes(raw[off:off + 4], "little", signed=True)
            demoted = raw[off + 4] != 0
            est = np.frombuffer(raw[off + 5:off + 13], "<f8")[0]
            ln = int.from_bytes(raw[off + 13:off + 15], "little")
            name = raw[off + 15:off + 15 + ln].decode(
                "utf-8", "surrogateescape")
            off += 15 + ln
            self._tenant_names[tid] = name
            out[name] = {"demoted": demoted, "key_est": float(est)}
        return out

    def tenant_restore(self, entries) -> int:
        """Restore quarantine state from a checkpoint: entries is an
        iterable of (name, demoted, key_est) in snapshot order — names
        re-intern in that order, reproducing the snapshot's ids. Returns
        entries applied."""
        parts = []
        for name, demoted, est in entries:
            nb = name.encode("utf-8", "surrogateescape")
            parts.append(bytes([1 if demoted else 0]))
            parts.append(np.float64(est).tobytes())
            parts.append(len(nb).to_bytes(2, "little"))
            parts.append(nb)
        blob = b"".join(parts)
        n = int(_lib.vt_tenant_restore(self._h, blob, len(blob)))
        self._tenant_refresh_names()
        return n

    def set_tenant(self, name: str) -> None:
        """Python-feed-path parse context: subsequent feed() calls parse
        as `name` (empty -> default tenant). The ring engine resolves
        identity itself in ring_push; this is for the fallback path and
        tests."""
        nb = name.encode("utf-8", "surrogateescape")
        _lib.vt_set_tenant(self._h, nb, len(nb))

    def tenant_rows_drain(self) -> dict:
        """Drain-and-reset the master parser's exact demoted-row counts
        ({tenant: rows}) staged by the Python feed path."""
        cap = 64
        while True:
            ids = (ctypes.c_int32 * cap)()
            counts = (ctypes.c_uint64 * cap)()
            n = _lib.vt_tenant_rows(self._h, ids, counts, cap)
            if n >= 0:
                break
            cap = -n * 2
        return {self._tenant_name(ids[i]): int(counts[i])
                for i in range(n)}

    def ring_tenant_drain_one(self, ring: int) -> dict:
        """Drain-and-reset ring i's exact per-tenant deltas:
        {tenant: {"admitted": {class: n}, "shed": {class: n},
        "demoted_rows": n}} with zero entries omitted. Callers must fold
        across ALL rings (ring_admission_drain_one / admission_drain do)."""
        cap = getattr(self, "_tenant_cap", 64)
        while True:
            ids = (ctypes.c_int32 * cap)()
            counts = (ctypes.c_uint64 * (cap * 7))()
            n = _lib.vrm_tenant_counters(self._rings, ring, ids, counts,
                                         cap)
            if n >= 0:
                break
            cap = -n * 2
        self._tenant_cap = cap
        names = ("self", "high", "low")
        out = {}
        for i in range(n):
            row = counts[i * 7:(i + 1) * 7]
            adm = {names[c]: int(row[c]) for c in range(3) if row[c]}
            shed = {names[c]: int(row[3 + c]) for c in range(3)
                    if row[3 + c]}
            ent = {}
            if adm:
                ent["admitted"] = adm
            if shed:
                ent["shed"] = shed
            if row[6]:
                ent["demoted_rows"] = int(row[6])
            if ent:
                out[self._tenant_name(ids[i])] = ent
        return out
