"""ctypes binding for the native core (``libhtpu_core.so``).

The reference binds C++ to Python through per-framework FFI (TF custom op
loading, torch pybind11/cffi, mxnet ctypes — SURVEY L2/L3). This build has
one framework-agnostic shared library and one binding mechanism: ctypes on
an ``extern "C"`` API (pybind11 is not in the image, per the environment
contract). The library is rebuilt on demand when the digest of its sources
differs from the one stored beside the binary — the role setup.py's
extension builders play in the reference.

Exports:
* ``NativeNegotiator`` — drop-in for ``ops.controller.Negotiator``
* ``NativeParameterManager`` — GP/Bayesian autotuner (parameter_manager.cc)
* ``NativeTimelineWriter`` — background-thread trace writer (timeline.cc)
* ``available()`` — whether the native core loaded
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
import threading
import time
from typing import List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "build", "libhtpu_core.so")
_DIGEST_PATH = _LIB_PATH + ".sources.sha256"
_SOURCES = ("negotiator.cc", "autotune.cc", "timeline_writer.cc",
            "controller_service.cc", "negotiator_core.h", "sha256.h",
            "Makefile")

_lib = None
_lib_lock = threading.Lock()
_load_error: Optional[str] = None


def _build_locked() -> None:
    """Serialize builds across processes: every rank of a fresh checkout may
    race into the first build (the launcher spawns them together); an
    exclusive flock makes one rank build while the rest wait, then re-check."""
    import fcntl

    os.makedirs(os.path.join(_DIR, "build"), exist_ok=True)
    lock_path = os.path.join(_DIR, "build", ".build.lock")
    with open(lock_path, "w", encoding="utf-8") as lock_fh:
        fcntl.flock(lock_fh, fcntl.LOCK_EX)
        try:
            if _needs_build():
                # -B: make compares mtimes, which say nothing about a
                # library that arrived with a copy of the tree
                subprocess.run(["make", "-B", "-C", _DIR], check=True,
                               capture_output=True, text=True, timeout=120)
                with open(_DIGEST_PATH, "w", encoding="utf-8") as fh:
                    fh.write(_sources_digest())
        finally:
            fcntl.flock(lock_fh, fcntl.LOCK_UN)


def _sources_digest() -> str:
    digest = hashlib.sha256()
    for src in _SOURCES:
        digest.update(src.encode())
        with open(os.path.join(_DIR, src), "rb") as fh:
            digest.update(fh.read())
    return digest.hexdigest()


def _needs_build() -> bool:
    """True unless the library was built from exactly these sources. Keyed
    on content, not mtimes: a ``build/`` directory carried along with a
    copy of the tree has arbitrary timestamps."""
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_DIGEST_PATH, encoding="utf-8") as fh:
            return fh.read() != _sources_digest()
    except FileNotFoundError:
        return True


def _load():
    global _lib, _load_error
    with _lib_lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            if _needs_build():
                _build_locked()
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, subprocess.SubprocessError) as exc:
            _load_error = str(exc)
            return None
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib) -> None:
    c = ctypes
    lib.htpu_negotiator_new.restype = c.c_void_p
    lib.htpu_negotiator_new.argtypes = [c.c_int, c.c_longlong, c.c_double,
                                        c.c_int]
    lib.htpu_negotiator_free.argtypes = [c.c_void_p]
    lib.htpu_negotiator_add_request.argtypes = [
        c.c_void_p, c.c_int, c.c_int, c.c_int, c.c_char_p, c.c_int, c.c_int,
        c.POINTER(c.c_longlong)]
    lib.htpu_negotiator_shutdown.argtypes = [c.c_void_p]
    lib.htpu_negotiator_set_fusion_threshold.argtypes = [c.c_void_p,
                                                         c.c_longlong]
    lib.htpu_negotiator_construct.restype = c.c_void_p  # manual free
    lib.htpu_negotiator_construct.argtypes = [c.c_void_p]
    lib.htpu_free.argtypes = [c.c_void_p]

    lib.htpu_param_manager_new.restype = c.c_void_p
    lib.htpu_param_manager_new.argtypes = [c.c_double, c.c_double, c.c_int,
                                           c.c_int]
    lib.htpu_param_manager_free.argtypes = [c.c_void_p]
    lib.htpu_param_manager_update.restype = c.c_int
    lib.htpu_param_manager_update.argtypes = [c.c_void_p, c.c_double,
                                              c.c_double]
    for fn in ("fusion_bytes", "cycle_ms", "best_fusion_bytes",
               "best_cycle_ms", "best_score"):
        getattr(lib, f"htpu_param_manager_{fn}").restype = c.c_double
        getattr(lib, f"htpu_param_manager_{fn}").argtypes = [c.c_void_p]

    lib.htpu_timeline_open.restype = c.c_void_p
    lib.htpu_timeline_open.argtypes = [c.c_char_p]
    lib.htpu_timeline_write.argtypes = [c.c_void_p, c.c_char_p]
    lib.htpu_timeline_close.argtypes = [c.c_void_p]

    lib.htpu_controller_start.restype = c.c_void_p
    lib.htpu_controller_start.argtypes = [
        c.c_int, c.c_char_p, c.c_int, c.c_char_p, c.c_int, c.c_longlong,
        c.c_double, c.c_int, c.c_char_p, c.c_int, c.c_char_p, c.c_char_p,
        c.c_int]
    lib.htpu_controller_port.restype = c.c_int
    lib.htpu_controller_port.argtypes = [c.c_void_p]
    lib.htpu_controller_world_shutdown.restype = c.c_int
    lib.htpu_controller_world_shutdown.argtypes = [c.c_void_p]
    lib.htpu_controller_drain_stats.restype = c.c_int
    lib.htpu_controller_drain_stats.argtypes = [
        c.c_void_p, c.POINTER(c.c_double), c.POINTER(c.c_double), c.c_int]
    lib.htpu_controller_set_tuning.argtypes = [c.c_void_p, c.c_longlong,
                                               c.c_double]
    lib.htpu_controller_stop.argtypes = [c.c_void_p]


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    _load()
    return _load_error


class NativeNegotiator:
    """Same interface as ``ops.controller.Negotiator``, backed by C++.

    Wire-compression codecs (``Request.codec``, the EQuARX int8/fp8 data
    plane) postdate the C++ core's request/response schema, so this
    wrapper keeps the codec bookkeeping in Python: codecs are recorded
    per tensor name at ``add_request_list`` time and stamped onto the
    constructed responses, with mixed-codec fused batches SPLIT into
    codec-pure sub-batches (the C++ fusion loop cannot key on a field it
    does not know). The negotiator runs once per world — on the
    controller service (or the size-1 local world) — and its ResponseList
    is what every rank executes, so the stamping is rank-consistent by
    construction. Cross-rank codec mismatches become coordinator ERROR
    responses, the same contract the Python ``Negotiator`` enforces for
    dtype and codec mismatches."""

    def __init__(self, size: int, fusion_threshold_bytes: int,
                 stall_warning_s: float = 60.0,
                 stall_check_disable: bool = False) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_load_error}")
        self._lib = lib
        self._codecs: dict = {}  # in-flight tensor name -> codec tag
        self._mismatched: dict = {}  # name -> (codec_a, codec_b)
        # Idle/hit-cycle bookkeeping for the response-cache bypass
        # (docs/response-cache.md): an all-ranks cache hit adds no
        # requests, so the only reason to cross the FFI boundary is the
        # interval-gated stall check (or a latched shutdown). Track both
        # in Python — same pattern PR 1 uses for codec bookkeeping the
        # C++ wire predates — so steady-state hit cycles skip the
        # construct FFI + JSON parse entirely between stall intervals.
        self._dirty = False
        self._shutdown_latched = False
        self._stall_warning_s = stall_warning_s
        self._stall_check_disable = stall_check_disable
        self._last_ffi_pass = time.monotonic()
        self._handle = lib.htpu_negotiator_new(
            size, fusion_threshold_bytes, stall_warning_s,
            1 if stall_check_disable else 0)

    def set_fusion_threshold(self, threshold_bytes: int) -> None:
        self._lib.htpu_negotiator_set_fusion_threshold(
            self._handle, int(threshold_bytes))

    def request_shutdown(self) -> None:
        """Force shutdown on subsequent response lists (stall-escalation
        path; same contract as ``Negotiator.request_shutdown``)."""
        self._shutdown_latched = True
        self._lib.htpu_negotiator_shutdown(self._handle)

    def add_request_list(self, rl) -> None:
        if rl.shutdown:
            self._shutdown_latched = True
            self._lib.htpu_negotiator_shutdown(self._handle)
        if rl.requests:
            self._dirty = True
        for req in rl.requests:
            # one (codec, apply-fingerprint) wire identity per tensor:
            # both postdate the C++ schema, so both ride this Python
            # bookkeeping and stamp onto the constructed responses
            wire = (getattr(req, "codec", "none"),
                    getattr(req, "apply_fingerprint", ""))
            prev = self._codecs.setdefault(req.tensor_name, wire)
            if prev != wire:
                self._mismatched.setdefault(req.tensor_name, (prev, wire))
            dims = (ctypes.c_longlong * len(req.tensor_shape))(
                *req.tensor_shape)
            self._lib.htpu_negotiator_add_request(
                self._handle, req.request_rank, int(req.request_type),
                int(req.tensor_type), req.tensor_name.encode("utf-8"),
                req.root_rank, len(req.tensor_shape), dims)

    def _stamp_codecs(self, responses):
        """Attach the negotiated (codec, apply-fingerprint) wire
        identities. Mixed-identity ALLREDUCE batches split into adjacent
        identity-pure runs (execution order preserved); cross-rank
        mismatches carve out per-tensor ERROR responses (the Python
        Negotiator's contract for codecs and fused-apply rules
        alike)."""
        from ..ops.messages import Response, ResponseType

        out: List = []
        for resp in responses:
            codecs = []
            for n in resp.tensor_names:
                codec = self._codecs.pop(n, ("none", ""))
                if n in self._mismatched:
                    (a, fa), (b, fb) = self._mismatched.pop(n)
                    what = "compression codecs" if a != b \
                        else "fused-apply rules"
                    one, other = (a, b) if a != b else (fa, fb)
                    codec = Response(
                        ResponseType.ERROR, tensor_names=[n],
                        error_message=(
                            f"Mismatched {what}: one rank sent {one!r}, "
                            f"another sent {other!r} for tensor {n}."))
                codecs.append(codec)
            if resp.response_type != ResponseType.ALLREDUCE:
                # non-fused ops carry one name; a mismatch there still
                # surfaces as the carved-out error
                if codecs and isinstance(codecs[0], Response):
                    out.append(codecs[0])
                    continue
                resp.tensor_codec = codecs[0][0] if codecs else "none"
                out.append(resp)
                continue
            start = 0
            bytes_left = resp.payload_bytes
            for i in range(1, len(codecs) + 1):
                if i < len(codecs) and codecs[i] == codecs[start] and \
                        not isinstance(codecs[start], Response):
                    continue
                if isinstance(codecs[start], Response):  # carved error
                    out.append(codecs[start])
                else:
                    out.append(Response(
                        ResponseType.ALLREDUCE,
                        tensor_names=resp.tensor_names[start:i],
                        tensor_dtype=resp.tensor_dtype,
                        # per-tensor bytes are unknown here; the batch
                        # total rides the FIRST non-error sub-batch so
                        # autotuner byte accounting stays conserved
                        # across the split
                        payload_bytes=bytes_left,
                        tensor_codec=codecs[start][0],
                        fused_apply=codecs[start][1]))
                    bytes_left = 0
                start = i
        return out

    def construct_response_list(self):
        from ..core.logging import LOG
        from ..ops.messages import ResponseList
        from .messages_adapter import parse_response_json

        if not self._dirty and not self._shutdown_latched and (
                self._stall_check_disable or
                time.monotonic() - self._last_ffi_pass
                < self._stall_warning_s):
            # Nothing added since the last construct and the stall-check
            # interval has not elapsed: the FFI call could only return an
            # empty list. stall_check=False is accurate — the check did
            # not run this cycle (the C++ core's own interval gate would
            # have declined it too).
            return ResponseList()
        self._dirty = False
        self._last_ffi_pass = time.monotonic()
        ptr = self._lib.htpu_negotiator_construct(self._handle)
        try:
            raw = ctypes.string_at(ptr).decode("utf-8")
        finally:
            self._lib.htpu_free(ptr)
        doc = json.loads(raw)
        for warning in doc.get("stall_warnings", []):
            LOG.warning("%s", warning)
        response_list = parse_response_json(doc)
        response_list.responses = self._stamp_codecs(
            response_list.responses)
        return response_list

    def __del__(self) -> None:
        handle = getattr(self, "_handle", None)
        if handle and getattr(self, "_lib", None) is not None:
            self._lib.htpu_negotiator_free(handle)
            self._handle = None


class NativeParameterManager:
    """GP/Bayesian autotuner over (fusion threshold, cycle time)."""

    def __init__(self, fusion_bytes: float, cycle_ms: float,
                 fusion_fixed: bool = False, cycle_fixed: bool = False) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_load_error}")
        self._lib = lib
        self._handle = lib.htpu_param_manager_new(
            fusion_bytes / (1024.0 * 1024.0), cycle_ms,
            1 if fusion_fixed else 0, 1 if cycle_fixed else 0)

    def update(self, bytes_processed: float, microseconds: float) -> bool:
        """Record a sample window; True when the knobs moved."""
        return bool(self._lib.htpu_param_manager_update(
            self._handle, bytes_processed, microseconds))

    @property
    def fusion_threshold_bytes(self) -> int:
        return int(self._lib.htpu_param_manager_fusion_bytes(self._handle))

    @property
    def cycle_time_ms(self) -> float:
        return self._lib.htpu_param_manager_cycle_ms(self._handle)

    @property
    def best(self) -> dict:
        return {
            "fusion_threshold_bytes": int(
                self._lib.htpu_param_manager_best_fusion_bytes(self._handle)),
            "cycle_time_ms":
                self._lib.htpu_param_manager_best_cycle_ms(self._handle),
            "score_bytes_per_us":
                self._lib.htpu_param_manager_best_score(self._handle),
        }

    def __del__(self) -> None:
        handle = getattr(self, "_handle", None)
        if handle and getattr(self, "_lib", None) is not None:
            self._lib.htpu_param_manager_free(handle)
            self._handle = None


class NativeTimelineWriter:
    """Background-thread trace writer; records are preformatted JSON."""

    def __init__(self, path: str) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native core unavailable: {_load_error}")
        self._lib = lib
        self._handle = lib.htpu_timeline_open(path.encode("utf-8"))
        if not self._handle:
            raise OSError(f"cannot open timeline file {path!r}")

    def write(self, record: str) -> None:
        self._lib.htpu_timeline_write(self._handle, record.encode("utf-8"))

    def close(self) -> None:
        if self._handle:
            self._lib.htpu_timeline_close(self._handle)
            self._handle = None

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass
