"""ctypes binding + build helper for the native PJRT predictor
(predictor.cc). See that file's header for the C surface; this wrapper
exists for tests and for python-side smoke use — the point of the
artifact is that C/C++ programs can run inference with NO Python, via
libptpu_predictor.so / the ptpu_predict demo binary.
"""
import ctypes
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libptpu_predictor.so")


def find_pjrt_include():
    """The official pjrt_c_api.h ships inside the tensorflow package —
    located via find_spec WITHOUT importing tensorflow (the import
    costs seconds and hundreds of MB for a header path)."""
    import importlib.util
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    inc = os.path.join(list(spec.submodule_search_locations)[0],
                       "include")
    return inc if os.path.exists(
        os.path.join(inc, "xla", "pjrt", "c", "pjrt_c_api.h")) else None


def find_plugin():
    """Best available PJRT C-API plugin .so on this machine."""
    cands = [os.environ.get("PTPU_PJRT_PLUGIN")]
    try:
        import libtpu
        cands.append(os.path.join(os.path.dirname(libtpu.__file__),
                                  "libtpu.so"))
    except Exception:
        pass
    for c in cands:
        if c and os.path.exists(c):
            return c
    return None


def build():
    """Build libptpu_predictor.so + ptpu_predict (returns False if the
    header or toolchain is unavailable — callers must degrade)."""
    inc = find_pjrt_include()
    if inc is None:
        return False
    try:
        subprocess.run(["make", "-C", _DIR, "predictor",
                        f"PJRT_INC={inc}"], check=True,
                       capture_output=True, timeout=180)
        return True
    except Exception:
        return False


_lib = None


def lib():
    global _lib
    if _lib is not None:
        return _lib
    if not os.path.exists(_SO) and not build():
        return None
    try:
        L = ctypes.CDLL(_SO)
    except OSError:
        return None
    L.ptpu_last_error.restype = ctypes.c_char_p
    L.ptpu_plugin_probe.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    L.ptpu_predictor_load.restype = ctypes.c_void_p
    L.ptpu_predictor_load.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    L.ptpu_predictor_num_inputs.argtypes = [ctypes.c_void_p]
    L.ptpu_predictor_num_outputs.argtypes = [ctypes.c_void_p]
    L.ptpu_predictor_output_bytes.restype = ctypes.c_long
    L.ptpu_predictor_output_bytes.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int]
    L.ptpu_predictor_run.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p)]
    L.ptpu_predictor_destroy.argtypes = [ctypes.c_void_p]
    _lib = L
    return L


# child body for the isolated probe: raw ctypes against the built .so,
# no paddle_tpu/jax import (keeps the sacrificial process cheap)
_PROBE_CHILD = """
import ctypes, json, sys
L = ctypes.CDLL(sys.argv[1])
L.ptpu_last_error.restype = ctypes.c_char_p
L.ptpu_plugin_probe.argtypes = [ctypes.c_char_p] + \
    [ctypes.POINTER(ctypes.c_int)] * 3
major = ctypes.c_int(-1); minor = ctypes.c_int(-1); ndev = ctypes.c_int(-1)
rc = L.ptpu_plugin_probe(sys.argv[2].encode(), ctypes.byref(major),
                         ctypes.byref(minor), ctypes.byref(ndev))
err = L.ptpu_last_error().decode("utf-8", "replace") if rc else ""
print(json.dumps([rc, major.value, minor.value, ndev.value, err]))
"""


def probe(plugin_path, isolate=True):
    """(rc, major, minor, num_devices, error) for a plugin .so.

    rc 0 = full client; 1 = plugin loaded, client create failed with a
    clean error; -1 = load failure; -2 = the plugin CRASHED during the
    probe. By default the probe runs in a sacrificial subprocess: a
    plugin that abort()s while loading must report as rc=-2, not take
    the whole caller process down.

    A probe CREATES a PJRT client, and a chip belongs to one process:
    with libtpu this only answers rc 0 from a process tree in which
    nobody — this caller included — has initialized JAX's TPU backend.
    Once the caller holds the chip the child reports rc 1 (or times
    out); that is the chip being busy, not a broken plugin."""
    L = lib()
    if L is None:
        return None
    if not isolate:
        major = ctypes.c_int(-1)
        minor = ctypes.c_int(-1)
        ndev = ctypes.c_int(-1)
        rc = L.ptpu_plugin_probe(plugin_path.encode(),
                                 ctypes.byref(major), ctypes.byref(minor),
                                 ctypes.byref(ndev))
        err = L.ptpu_last_error().decode("utf-8", "replace") if rc else ""
        return rc, major.value, minor.value, ndev.value, err
    import json
    import sys
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_CHILD, _SO, plugin_path],
            capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        return -2, -1, -1, -1, "plugin probe timed out"
    if proc.returncode == 0 and proc.stdout.strip():
        return tuple(json.loads(proc.stdout.strip().splitlines()[-1]))
    return (-2, -1, -1, -1,
            f"plugin crashed during probe (exit {proc.returncode}): "
            f"{proc.stderr.strip()[-300:]}")


class NativePredictor:
    """Python-side handle over the C predictor (tests/smoke only)."""

    def __init__(self, model_dir, plugin_path=None):
        import numpy as np
        self._np = np
        L = lib()
        if L is None:
            raise RuntimeError("native predictor unavailable "
                               "(header/toolchain missing)")
        plugin_path = plugin_path or find_plugin()
        if plugin_path is None:
            raise RuntimeError("no PJRT plugin found")
        self._L = L
        self._h = L.ptpu_predictor_load(plugin_path.encode(),
                                        model_dir.encode())
        if not self._h:
            raise RuntimeError("load failed: "
                               + L.ptpu_last_error().decode())
        self.num_inputs = L.ptpu_predictor_num_inputs(self._h)
        self.num_outputs = L.ptpu_predictor_num_outputs(self._h)

    def run(self, input_arrays):
        import time
        from .. import telemetry as _tm
        if len(input_arrays) != self.num_inputs:
            raise ValueError(
                f"model takes {self.num_inputs} inputs, "
                f"got {len(input_arrays)}")
        t0 = time.perf_counter()
        with _tm.span("native_predictor.run", inputs=len(input_arrays)):
            outs = self._run_impl(input_arrays)
        if _tm.enabled():
            _tm.counter("native_predictor.requests").inc()
            _tm.histogram("native_predictor.latency_seconds").observe(
                time.perf_counter() - t0)
        return outs

    def _run_impl(self, input_arrays):
        np = self._np
        ins = [np.ascontiguousarray(a) for a in input_arrays]
        in_ptrs = (ctypes.c_void_p * len(ins))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in ins])
        outs = []
        out_ptrs = (ctypes.c_void_p * self.num_outputs)()
        for i in range(self.num_outputs):
            nb = self._L.ptpu_predictor_output_bytes(self._h, i)
            buf = np.zeros(nb, np.uint8)
            outs.append(buf)
            out_ptrs[i] = buf.ctypes.data_as(ctypes.c_void_p).value
        rc = self._L.ptpu_predictor_run(self._h, in_ptrs, out_ptrs)
        if rc:
            raise RuntimeError("run failed: "
                               + self._L.ptpu_last_error().decode())
        return outs  # raw bytes per output; caller views by dtype

    def close(self):
        if getattr(self, "_h", None):
            self._L.ptpu_predictor_destroy(self._h)
            self._h = None
