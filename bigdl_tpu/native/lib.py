"""Build-on-first-use loader + ctypes wrappers + numpy fallbacks."""

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.native")

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native", "bigdl_tpu_io.cpp")
# build products stay inside the checkout (a .gitignore'd directory): a
# library built from another checkout's source can never be picked up
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_build")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-march=native"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    if not os.path.exists(_SRC):
        log.warning("native source %s not found; numpy fallbacks in use",
                    _SRC)
        return None
    # the artifact is named by the CONTENT of its source (and the flags):
    # an edited source can never load a stale library, whatever the mtimes
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(_CXX).encode()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libbigdl_tpu_io-{digest}.so")
    if not os.path.exists(so):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        base = _CXX + ["-o", tmp, _SRC]
        # with libjpeg if the box has it; every other op still builds
        # without (python decode falls back to PIL)
        errors = []
        for cmd in (base + ["-lpthread", "-ljpeg"],
                    base + ["-DBTIO_NO_JPEG", "-lpthread"]):
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=120)
                os.replace(tmp, so)
                break
            except subprocess.CalledProcessError as e:
                errors.append(e.stderr.decode(errors="replace")[-300:])
            except (subprocess.SubprocessError, OSError) as e:
                errors.append(str(e))
        else:
            log.warning("native library build failed (%s); numpy "
                        "fallbacks in use", " | ".join(errors))
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log.warning("native library %s failed to load (%s); numpy "
                    "fallbacks in use", so, e)
        return None
    # signatures
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.btio_resize_bilinear_u8.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, u8p, ctypes.c_int,
        ctypes.c_int]
    lib.btio_crop_u8.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, u8p, ctypes.c_int, ctypes.c_int]
    lib.btio_hflip_u8.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int]
    lib.btio_normalize_f32.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p, f32p, f32p]
    lib.btio_pipeline_create.argtypes = [ctypes.c_int]
    lib.btio_pipeline_create.restype = ctypes.c_void_p
    lib.btio_pipeline_destroy.argtypes = [ctypes.c_void_p]
    lib.btio_process_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(u8p), i32p, i32p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p, f32p, f32p]
    lib.btio_gather_rows_f32.argtypes = [
        ctypes.c_void_p, f32p, i64p, ctypes.c_int, ctypes.c_int64, f32p]
    lib.btio_records_open.argtypes = [ctypes.c_char_p]
    lib.btio_records_open.restype = ctypes.c_void_p
    lib.btio_records_count.argtypes = [ctypes.c_void_p]
    lib.btio_records_count.restype = ctypes.c_int64
    lib.btio_records_bytes.argtypes = [ctypes.c_void_p]
    lib.btio_records_bytes.restype = ctypes.c_int64
    lib.btio_records_gather.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64p, ctypes.c_int, u8p]
    lib.btio_records_close.argtypes = [ctypes.c_void_p]
    lib.btio_jpeg_available.restype = ctypes.c_int
    lib.btio_jpeg_dims.argtypes = [u8p, ctypes.c_int64, i32p, i32p, i32p]
    lib.btio_jpeg_dims.restype = ctypes.c_int
    lib.btio_jpeg_decode.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int,
                                     ctypes.c_int]
    lib.btio_jpeg_decode.restype = ctypes.c_int
    lib.btio_decode_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(u8p), i64p, i32p,
        ctypes.c_int, ctypes.c_int, f32p, f32p, f32p, i32p]
    lib.btio_version.restype = ctypes.c_int
    if lib.btio_version() != 4:
        log.warning("native library %s reports ABI version %s, want 4; "
                    "numpy fallbacks in use", so, lib.btio_version())
        return None
    return lib


def _get() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is None and not _tried:
        with _lock:
            if _lib is None and not _tried:
                _lib = _build_and_load()
                _tried = True
    return _lib


def available() -> bool:
    return _get() is not None


def _u8p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _f32p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


# ---------------------------------------------------------------------------
# Single-image ops (uint8 HWC)
# ---------------------------------------------------------------------------

def resize_bilinear(img: np.ndarray, oh: int, ow: int) -> np.ndarray:
    img = np.ascontiguousarray(img, np.uint8)
    h, w, c = img.shape
    if (h, w) == (oh, ow):
        return img
    lib = _get()
    if lib is not None:
        out = np.empty((oh, ow, c), np.uint8)
        lib.btio_resize_bilinear_u8(_u8p(img), h, w, c, _u8p(out), oh, ow)
        return out
    # numpy fallback (same align-corners-style sampling as the C path)
    ys = (np.linspace(0, h - 1, oh) if oh > 1 else np.zeros(1))
    xs = (np.linspace(0, w - 1, ow) if ow > 1 else np.zeros(1))
    y0 = ys.astype(np.int64)
    x0 = xs.astype(np.int64)
    y1 = np.minimum(y0 + 1, h - 1)
    x1 = np.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[:, None, None]
    wx = (xs - x0)[None, :, None]
    f = img.astype(np.float32)
    top = f[y0][:, x0] * (1 - wx) + f[y0][:, x1] * wx
    bot = f[y1][:, x0] * (1 - wx) + f[y1][:, x1] * wx
    return np.rint(top * (1 - wy) + bot * wy).astype(np.uint8)


def _check_crop(h, w, oy, ox, ch, cw):
    if oy < 0 or ox < 0 or oy + ch > h or ox + cw > w:
        raise ValueError(
            f"crop ({ch}x{cw} at {oy},{ox}) out of bounds for {h}x{w} image"
            " — resize up first (the C path would read out of bounds)")


def crop(img: np.ndarray, oy: int, ox: int, ch: int, cw: int) -> np.ndarray:
    img = np.ascontiguousarray(img, np.uint8)
    h, w, c = img.shape
    _check_crop(h, w, oy, ox, ch, cw)
    lib = _get()
    if lib is not None:
        out = np.empty((ch, cw, c), np.uint8)
        lib.btio_crop_u8(_u8p(img), h, w, c, oy, ox, _u8p(out), ch, cw)
        return out
    return img[oy:oy + ch, ox:ox + cw].copy()


def hflip(img: np.ndarray) -> np.ndarray:
    src = np.asarray(img)
    out = np.ascontiguousarray(src, np.uint8)
    if out is src:  # ascontiguousarray didn't copy — keep input unmutated
        out = out.copy()
    lib = _get()
    if lib is not None:
        h, w, c = out.shape
        lib.btio_hflip_u8(_u8p(out), h, w, c)
        return out
    return out[:, ::-1].copy()


def normalize(img: np.ndarray, mean, std) -> np.ndarray:
    """uint8 HWC -> float32 HWC, (x/255 - mean) / std per channel."""
    img = np.ascontiguousarray(img, np.uint8)
    h, w, c = img.shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib = _get()
    if lib is not None:
        out = np.empty((h, w, c), np.float32)
        lib.btio_normalize_f32(_u8p(img), h, w, c, _f32p(mean), _f32p(std),
                               _f32p(out))
        return out
    return ((img.astype(np.float32) / 255.0 - mean) / std).astype(np.float32)


# ---------------------------------------------------------------------------
# Threaded batch pipeline
# ---------------------------------------------------------------------------

class BatchPipeline:
    """Threaded per-image transform → contiguous NHWC f32 batch assembly.

    Reference analog: per-executor ``ThreadPool.invokeAndWait`` over
    transformer chains inside ``SampleToMiniBatch`` (SURVEY.md §4.1)."""

    def __init__(self, num_threads: Optional[int] = None):
        self.num_threads = num_threads or max(1, (os.cpu_count() or 2) - 1)
        lib = _get()
        self._lib = lib
        self._pipe = (lib.btio_pipeline_create(self.num_threads)
                      if lib is not None else None)

    def close(self):
        if self._pipe is not None:
            self._lib.btio_pipeline_destroy(self._pipe)
            self._pipe = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @staticmethod
    def _out_buffer(out, n, oh, ow, c) -> np.ndarray:
        """Validate a caller-provided output buffer (a ring slot — the
        no-per-batch-allocation path of data/pipeline.py) or allocate one."""
        if out is None:
            return np.empty((n, oh, ow, c), np.float32)
        if out.shape != (n, oh, ow, c) or out.dtype != np.float32 \
                or not out.flags.c_contiguous:
            raise ValueError(
                f"out buffer must be C-contiguous float32 {(n, oh, ow, c)}, "
                f"got {out.dtype} {out.shape}")
        return out

    def process_batch(self, images, out_hw, mean, std, resize_hw=None,
                      crops=None, flips=None, out=None) -> np.ndarray:
        """images: list of uint8 HWC arrays (same channel count).
        out_hw: (oh, ow) final size.  resize_hw: per-image or single (rh, rw)
        intermediate resize (None = no resize).  crops: per-image (cy, cx)
        offsets (None = 0,0).  flips: per-image bool (None = no flip).
        out: optional preallocated (n, oh, ow, c) float32 destination
        (a reusable ring slot); allocated fresh when None.
        Returns (n, oh, ow, c) float32, normalized."""
        n = len(images)
        oh, ow = out_hw
        c = images[0].shape[2]
        mean = np.ascontiguousarray(mean, np.float32)
        std = np.ascontiguousarray(std, np.float32)
        images = [np.ascontiguousarray(im, np.uint8) for im in images]

        if self._pipe is not None:
            out = self._out_buffer(out, n, oh, ow, c)
            srcs = (ctypes.POINTER(ctypes.c_uint8) * n)(
                *[_u8p(im) for im in images])
            dims = np.empty((n, 2), np.int32)
            geom = np.zeros((n, 5), np.int32)
            for i, im in enumerate(images):
                dims[i] = im.shape[:2]
                eh, ew = im.shape[:2]  # size entering the crop stage
                if resize_hw is not None:
                    rh, rw = (resize_hw[i]
                              if not np.isscalar(resize_hw[0]) else resize_hw)
                    geom[i, 0], geom[i, 1] = rh, rw
                    eh, ew = rh, rw
                cy, cx = crops[i] if crops is not None else (0, 0)
                _check_crop(eh, ew, cy, cx, oh, ow)
                if crops is not None:
                    geom[i, 2], geom[i, 3] = crops[i]
                if flips is not None:
                    geom[i, 4] = int(bool(flips[i]))
            self._lib.btio_process_batch(
                self._pipe, n, srcs,
                dims.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                geom.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                c, oh, ow, _f32p(mean), _f32p(std), _f32p(out))
            return out

        # fallback: sequential numpy
        out = self._out_buffer(out, n, oh, ow, c)
        for i, im in enumerate(images):
            cur = im
            if resize_hw is not None:
                rh, rw = (resize_hw[i]
                          if not np.isscalar(resize_hw[0]) else resize_hw)
                cur = resize_bilinear(cur, rh, rw)
            cy, cx = crops[i] if crops is not None else (0, 0)
            _check_crop(cur.shape[0], cur.shape[1], cy, cx, oh, ow)
            if cur.shape[:2] != (oh, ow) or (cy, cx) != (0, 0):
                cur = cur[cy:cy + oh, cx:cx + ow]
            if flips is not None and flips[i]:
                cur = cur[:, ::-1]
            out[i] = (cur.astype(np.float32) / 255.0 - mean) / std
        return out

    def decode_batch(self, encoded, out_hw, mean, std, resize_hw=None,
                     crops=None, flips=None, out=None) -> np.ndarray:
        """JPEG decode + transform, fully in C++ worker threads.

        ``encoded``: list of ``bytes`` (JPEG).  Remaining args as in
        ``process_batch`` (including the ``out=`` ring-slot destination).
        Returns (n, oh, ow, 3) float32.  Falls back to PIL +
        ``process_batch`` when the native lib lacks libjpeg.
        Raises ValueError naming the failing index on a corrupt image."""
        n = len(encoded)
        oh, ow = out_hw
        if self._pipe is None or not jpeg_available():
            return self.process_batch([decode_jpeg(e) for e in encoded],
                                      out_hw, mean, std, resize_hw=resize_hw,
                                      crops=crops, flips=flips, out=out)
        mean = np.ascontiguousarray(mean, np.float32)
        std = np.ascontiguousarray(std, np.float32)
        bufs = [np.frombuffer(e, np.uint8) for e in encoded]
        srcs = (ctypes.POINTER(ctypes.c_uint8) * n)(
            *[_u8p(b) for b in bufs])
        lens = np.asarray([len(e) for e in encoded], np.int64)
        geom = np.zeros((n, 5), np.int32)
        for i in range(n):
            if resize_hw is not None:
                rh, rw = (resize_hw[i]
                          if not np.isscalar(resize_hw[0]) else resize_hw)
                geom[i, 0], geom[i, 1] = rh, rw
            if crops is not None:
                geom[i, 2], geom[i, 3] = crops[i]
            if flips is not None:
                geom[i, 4] = int(bool(flips[i]))
        out = self._out_buffer(out, n, oh, ow, 3)
        status = np.empty((n,), np.int32)
        self._lib.btio_decode_batch(
            self._pipe, n, srcs,
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            geom.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            oh, ow, _f32p(mean), _f32p(std), _f32p(out),
            status.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        bad_decode = np.flatnonzero(status == -1)
        bad_crop = np.flatnonzero(status == -2)
        if len(bad_crop):
            raise ValueError(
                "crop out of bounds of the decoded/resized image for batch "
                f"indices {bad_crop.tolist()[:8]} — pass resize_hw or "
                "shrink the crop (geometry bug, not corrupt data)")
        if len(bad_decode):
            raise ValueError(
                f"JPEG decode failed for batch indices "
                f"{bad_decode.tolist()[:8]}")
        return out

    def gather_rows(self, src: np.ndarray, idx: np.ndarray) -> np.ndarray:
        """Parallel src[idx] for a 2-D-viewable float32 array (batch
        assembly from a sample pool)."""
        src = np.ascontiguousarray(src, np.float32)
        idx = np.ascontiguousarray(idx, np.int64)
        if self._pipe is None:
            return src[idx].copy()
        row = int(np.prod(src.shape[1:]))
        out = np.empty((len(idx),) + src.shape[1:], np.float32)
        self._lib.btio_gather_rows_f32(
            self._pipe, _f32p(src),
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(idx), row, _f32p(out))
        return out


class RecordReader:
    """Memory-mapped fixed-size-record reader over the native lib (the
    data-loader executor) with threaded batch gather; ``None`` handle when
    the lib is unavailable (callers fall back to np.memmap)."""

    def __init__(self, path: str, pipeline: "BatchPipeline" = None):
        lib = _get()
        self._lib = lib
        self._h = lib.btio_records_open(
            os.fsencode(path)) if lib is not None else None
        if lib is not None and not self._h:
            raise ValueError(f"not a BTRECv1 record file: {path}")
        self._pipe = pipeline

    @property
    def ok(self) -> bool:
        return self._h is not None

    def count(self) -> int:
        return int(self._lib.btio_records_count(self._h))

    def record_bytes(self) -> int:
        return int(self._lib.btio_records_bytes(self._h))

    def gather(self, idx: np.ndarray, out=None) -> np.ndarray:
        """(n,) int64 indices -> (n, record_bytes) uint8.  ``out``: optional
        preallocated destination (a reusable read-stage buffer)."""
        idx = np.ascontiguousarray(idx, np.int64)
        shape = (len(idx), self.record_bytes())
        if out is None:
            out = np.empty(shape, np.uint8)
        elif out.shape != shape or out.dtype != np.uint8 \
                or not out.flags.c_contiguous:
            raise ValueError(
                f"out buffer must be C-contiguous uint8 {shape}, got "
                f"{out.dtype} {out.shape}")
        self._lib.btio_records_gather(
            self._h, self._pipe._pipe if self._pipe is not None else None,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(idx),
            _u8p(out))
        return out

    def close(self):
        if self._h is not None:
            self._lib.btio_records_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def jpeg_available() -> bool:
    """True when the native lib was built against libjpeg."""
    lib = _get()
    return bool(lib is not None and lib.btio_jpeg_available())


def decode_jpeg(data: bytes) -> np.ndarray:
    """Decode one JPEG to (h, w, 3) RGB uint8 — native libjpeg when
    available, PIL otherwise.  Raises ValueError on corrupt input."""
    lib = _get()
    if lib is not None and lib.btio_jpeg_available():
        buf = np.frombuffer(data, np.uint8)
        h = ctypes.c_int32()
        w = ctypes.c_int32()
        c = ctypes.c_int32()
        i32p_ = ctypes.POINTER(ctypes.c_int32)
        if lib.btio_jpeg_dims(_u8p(buf), len(data), ctypes.byref(h),
                              ctypes.byref(w), ctypes.byref(c)) == 0:
            out = np.empty((h.value, w.value, 3), np.uint8)
            if lib.btio_jpeg_decode(_u8p(buf), len(data), _u8p(out),
                                    h.value, w.value) == 0:
                return out
        raise ValueError("corrupt or unsupported JPEG")
    import io

    from PIL import Image

    try:
        with Image.open(io.BytesIO(data)) as im:
            return np.asarray(im.convert("RGB"), np.uint8)
    except Exception as e:
        raise ValueError(f"corrupt or unsupported JPEG: {e}") from None
