"""ctypes bindings for the C++ PJRT bridge (native/pjrt_bridge/bridge.cc).

The bridge is the production seam: a non-Python worker (the reference's Go
eval worker) links `libnomad_tpu_bridge.so`, feeds it a StableHLO program
exported once from JAX, and runs the placement kernels on the TPU without
a Python runtime.  These bindings exist to TEST that seam from the
in-process harness: export kernel → compile via the C++ bridge → execute
on the PJRT plugin → compare against the in-process JAX result.

Program export: `export_stablehlo(jit_fn, *args)` (jax.jit lowering →
StableHLO text).  Compile options: a serialized xla.CompileOptionsProto —
produced by jaxlib when available, else a hand-encoded minimal proto
(num_replicas=1, num_partitions=1; protobuf wire format is stable).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Sequence

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BRIDGE_SO = os.path.join(REPO_ROOT, "native", "build",
                         "libnomad_tpu_bridge.so")

# PJRT_Buffer_Type values (pjrt_c_api.h; stable across API versions)
_PJRT_TYPE = {
    np.dtype(np.bool_): 1,    # PRED
    np.dtype(np.int8): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7,
    np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10,
    np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
}


def build_bridge() -> bool:
    """Build the .so if missing; True when available."""
    if os.path.exists(BRIDGE_SO):
        return True
    try:
        subprocess.run(["make"], cwd=os.path.join(REPO_ROOT, "native"),
                       check=True, capture_output=True, timeout=300)
    except Exception:  # noqa: BLE001 - caller skips when unavailable
        return False
    return os.path.exists(BRIDGE_SO)


def bridge_available(plugin: Optional[str] = None) -> bool:
    """True when `plugin` names a PJRT plug-in library that exists and
    the bridge builds.  There is no default plug-in: the bridge opens
    its OWN PJRT client, and a process whose JAX engine already holds
    the chip cannot open a second one on it — so the path is always the
    caller's explicit choice."""
    return bool(plugin) and os.path.exists(plugin) and build_bridge()


def compile_options_bytes() -> bytes:
    """Serialized xla.CompileOptionsProto for a 1-replica 1-partition
    program."""
    try:
        from jax._src.lib import xla_client
        opts = xla_client.CompileOptions()
        opts.num_replicas = 1
        opts.num_partitions = 1
        return opts.SerializeAsString()
    except Exception:  # noqa: BLE001 - fall through to hand encoding
        pass
    # CompileOptionsProto { executable_build_options(3) {
    #     num_replicas(4)=1  num_partitions(5)=1 } }
    # (device_ordinal is left at its proto default; ntb_execute pins
    # execution to device 0 regardless)
    ebo = bytes([0x20, 0x01, 0x28, 0x01])
    return bytes([0x1A, len(ebo)]) + ebo


def export_stablehlo(fn, *args) -> bytes:
    """jit-lower `fn` at `args`' shapes and return StableHLO MLIR text.

    keep_unused=True: the bridge caller feeds EVERY leaf of `args` as an
    execute buffer, but jit's default drops parameters the kernel never
    reads from the lowered signature — the argument-count mismatch then
    kills the raw PJRT execute (the compact multi-eval kernel reads only
    a subset of MultiEvalInputs; debugged round 5)."""
    import jax
    lowered = jax.jit(fn, keep_unused=True).lower(*args)
    return lowered.as_text().encode()


class BridgeError(RuntimeError):
    pass


class PjrtBridge:
    """One PJRT client owned by the C++ bridge library."""

    def __init__(self, plugin: str,
                 options: Optional[dict] = None) -> None:
        """`options`: plug-in create-options keyed by name (str or int
        values), for plug-ins that need them."""
        if not build_bridge():
            raise BridgeError("bridge library unavailable (build failed)")
        lib = ctypes.CDLL(BRIDGE_SO)
        lib.ntb_create_with_options.restype = ctypes.c_void_p
        lib.ntb_create_with_options.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),    # names
            ctypes.POINTER(ctypes.c_int),       # types
            ctypes.POINTER(ctypes.c_char_p),    # str_vals
            ctypes.POINTER(ctypes.c_int64),     # int_vals
            ctypes.c_char_p, ctypes.c_size_t]
        lib.ntb_destroy.argtypes = [ctypes.c_void_p]
        lib.ntb_device_count.argtypes = [ctypes.c_void_p]
        lib.ntb_device_count.restype = ctypes.c_int
        lib.ntb_platform.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_size_t]
        lib.ntb_platform.restype = ctypes.c_int
        lib.ntb_compile.restype = ctypes.c_void_p
        lib.ntb_compile.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.ntb_executable_destroy.argtypes = [ctypes.c_void_p,
                                               ctypes.c_void_p]
        lib.ntb_num_outputs.restype = ctypes.c_long
        lib.ntb_num_outputs.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_char_p, ctypes.c_size_t]
        lib.ntb_execute.restype = ctypes.c_int
        lib.ntb_execute.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),       # dtypes
            ctypes.POINTER(ctypes.c_int64),     # dims_flat
            ctypes.POINTER(ctypes.c_int),       # ndims
            ctypes.POINTER(ctypes.c_void_p),    # data
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p),    # out_data
            ctypes.POINTER(ctypes.c_int64),     # out_cap
            ctypes.POINTER(ctypes.c_int64),     # out_dims_flat
            ctypes.POINTER(ctypes.c_int),       # out_ndims
            ctypes.POINTER(ctypes.c_int),       # out_elem
            ctypes.POINTER(ctypes.c_int64),     # out_sizes
            ctypes.c_char_p, ctypes.c_size_t]
        # persistent device buffers (round-5 verdict #4)
        lib.ntb_upload.restype = ctypes.c_void_p
        lib.ntb_upload.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
        lib.ntb_buffer_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.ntb_execute_resident.restype = ctypes.c_int
        lib.ntb_execute_resident.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_char_p, ctypes.c_size_t]
        lib.ntb_fetch.restype = ctypes.c_int64
        lib.ntb_fetch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t]
        self._lib = lib
        self._err = ctypes.create_string_buffer(4096)
        opts = options or {}
        n = len(opts)
        names = (ctypes.c_char_p * max(n, 1))(
            *[k.encode() for k in opts])
        types = (ctypes.c_int * max(n, 1))(
            *[0 if isinstance(v, str) else 1 for v in opts.values()])
        strs = (ctypes.c_char_p * max(n, 1))(
            *[v.encode() if isinstance(v, str) else None
              for v in opts.values()])
        ints = (ctypes.c_int64 * max(n, 1))(
            *[0 if isinstance(v, str) else int(v) for v in opts.values()])
        self._h = lib.ntb_create_with_options(
            plugin.encode(), n, names, types, strs, ints, self._err, 4096)
        if not self._h:
            raise BridgeError(f"ntb_create: {self._err.value.decode()}")
        self._execs: List[int] = []

    # ------------------------------------------------------------- intro

    def device_count(self) -> int:
        return self._lib.ntb_device_count(self._h)

    def platform(self) -> str:
        buf = ctypes.create_string_buffer(256)
        if self._lib.ntb_platform(self._h, buf, 256) != 0:
            raise BridgeError(buf.value.decode())
        return buf.value.decode()

    # ----------------------------------------------------------- compile

    def compile(self, stablehlo: bytes,
                options: Optional[bytes] = None) -> int:
        opts = options if options is not None else compile_options_bytes()
        h = self._lib.ntb_compile(self._h, stablehlo, len(stablehlo),
                                  opts, len(opts), self._err, 4096)
        if not h:
            raise BridgeError(f"compile: {self._err.value.decode()}")
        self._execs.append(h)
        return h

    def num_outputs(self, exec_h: int) -> int:
        n = self._lib.ntb_num_outputs(self._h, exec_h, self._err, 4096)
        if n < 0:
            raise BridgeError(self._err.value.decode())
        return n

    # ----------------------------------------------------------- execute

    def execute(self, exec_h: int, inputs: Sequence[np.ndarray],
                out_specs: Sequence[tuple]) -> List[np.ndarray]:
        """`out_specs`: (shape, dtype) per output, in program order."""
        n_in = len(inputs)
        arrs = [np.ascontiguousarray(a) for a in inputs]
        dtypes = (ctypes.c_int * n_in)(
            *[_PJRT_TYPE[a.dtype] for a in arrs])
        dims = [d for a in arrs for d in a.shape]
        dims_flat = (ctypes.c_int64 * max(len(dims), 1))(*dims)
        ndims = (ctypes.c_int * n_in)(*[a.ndim for a in arrs])
        data = (ctypes.c_void_p * n_in)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])

        n_out = len(out_specs)
        outs = [np.empty(shape, dtype=dtype) for shape, dtype in out_specs]
        out_data = (ctypes.c_void_p * n_out)(
            *[o.ctypes.data_as(ctypes.c_void_p).value for o in outs])
        out_cap = (ctypes.c_int64 * n_out)(*[o.nbytes for o in outs])
        odims = [d for o in outs for d in o.shape]
        out_dims_flat = (ctypes.c_int64 * max(len(odims), 1))(*odims)
        out_ndims = (ctypes.c_int * n_out)(*[o.ndim for o in outs])
        out_elem = (ctypes.c_int * n_out)(*[o.itemsize for o in outs])
        out_sizes = (ctypes.c_int64 * n_out)()

        rc = self._lib.ntb_execute(
            self._h, exec_h, n_in, dtypes, dims_flat, ndims, data,
            n_out, out_data, out_cap, out_dims_flat, out_ndims, out_elem,
            out_sizes, self._err, 4096)
        if rc != 0:
            raise BridgeError(f"execute: {self._err.value.decode()}")
        for i, o in enumerate(outs):
            if out_sizes[i] != o.nbytes:
                raise BridgeError(
                    f"output {i}: got {out_sizes[i]} bytes, "
                    f"expected {o.nbytes}")
        return outs

    # ------------------------------------- persistent device buffers
    # (round-5 verdict #4: the production worker holds node tensors
    # DEVICE-RESIDENT and ships only per-wave deltas + the compact
    # result — ntb_execute's per-call re-upload was the 4× gap vs the
    # JAX-driven path)

    def upload(self, arr: np.ndarray) -> int:
        """Upload one host array; returns a retained device-buffer
        handle (free with buffer_free, or feed to execute_resident)."""
        a = np.ascontiguousarray(arr)
        dims = (ctypes.c_int64 * max(a.ndim, 1))(*a.shape)
        h = self._lib.ntb_upload(
            self._h, _PJRT_TYPE[a.dtype], dims, a.ndim,
            a.ctypes.data_as(ctypes.c_void_p), self._err, 4096)
        if not h:
            raise BridgeError(f"upload: {self._err.value.decode()}")
        return h

    def buffer_free(self, buf_h: int) -> None:
        self._lib.ntb_buffer_free(self._h, buf_h)

    def execute_resident(self, exec_h: int, in_handles: Sequence[int],
                         n_out: int) -> List[int]:
        """Execute with device-resident inputs; outputs stay on device
        and come back as retained handles (chainable into later
        executes — e.g. the proposed-usage tensor across waves)."""
        n_in = len(in_handles)
        ins = (ctypes.c_void_p * max(n_in, 1))(*in_handles)
        outs = (ctypes.c_void_p * max(n_out, 1))()
        rc = self._lib.ntb_execute_resident(
            self._h, exec_h, n_in, ins, n_out, outs, self._err, 4096)
        if rc != 0:
            raise BridgeError(
                f"execute_resident: {self._err.value.decode()}")
        return [outs[i] for i in range(n_out)]

    def fetch(self, buf_h: int, shape, dtype) -> np.ndarray:
        """Fetch one device buffer to host (dense row-major)."""
        out = np.empty(shape, dtype=dtype)
        size = self._lib.ntb_fetch(
            self._h, buf_h, out.ctypes.data_as(ctypes.c_void_p),
            out.nbytes, self._err, 4096)
        if size < 0:
            raise BridgeError(f"fetch: {self._err.value.decode()}")
        if size != out.nbytes:
            raise BridgeError(
                f"fetch: got {size} bytes, expected {out.nbytes}")
        return out

    # ------------------------------------------------------------- close

    def close(self) -> None:
        if self._h:
            for e in self._execs:
                self._lib.ntb_executable_destroy(self._h, e)
            self._execs.clear()
            self._lib.ntb_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
