"""Native codec loader: build-on-demand CPython extension + fallback hooks.

The wire format's reference implementation is the pure-Python
:mod:`serializer`; ``native/copycat_codec.c`` is a byte-identical C
walk of the same object graphs (the reference's serializer ran on the
JVM JIT — this is the equivalent native runtime component, SURVEY.md
§2.3 "serialization"). Loading degrades gracefully: no toolchain (or a
build failure) leaves ``codec()`` returning None and every caller on
the Python path.

The extension sees the LIVE registries from serializer.py (the
``@serialize_with`` decorator mutates them; C reads them per lookup).
They give each class one of three shapes (``serializer._CODEC_FIELDS``):
generic fields and a fixed head followed by generic fields (the log
entries: raw ``i64 index, i64 term, f64 timestamp``) are walked in C
from end to end; for classes with any other hand-written
write_object/read_object the walk re-enters Python through two
callbacks:

- ``encode_body(obj) -> bytes`` — the body after the 16+id tag;
- ``decode_body(cls, data, pos) -> (obj, new_pos)``.

Each such call counts in ``codec.python_bodies`` (``METRICS``, in the
tracer's report under the prefix ``codec.``): on the write path of a
replicated, persisted entry it reads 0 but for the ``Address`` of a
request or a reply.

Anything the C path can't express (ints beyond 64 bits, unregistered
types, an entry head that does not fit raw 64-bit fields) raises
``Fallback`` and Serializer.write/read re-run pure Python — the native
path is an accelerator, never a semantic fork.

The binary is git-ignored and rebuilt when the source is newer OR when
it does not state the ``_ABI`` this module needs: an older binary would
walk a class shape it does not know as plain fields and write other
bytes, so it is never loaded.
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import pathlib
import subprocess
import warnings
from typing import Any

from ..utils.metrics import MetricsRegistry
from ..utils.tracing import TRACER

#: what ``native/copycat_codec.c`` must state (``CODEC_ABI``): 2 = the
#: fixed head, ``configure``'s 7th argument
_ABI = 2

#: ``python_bodies``: calls of encode_body/decode_body, i.e. how often
#: the native walk re-entered Python for a custom class
METRICS = MetricsRegistry()
_python_bodies = METRICS.counter("python_bodies")
TRACER.register(METRICS, "codec.")

_NATIVE_DIR = pathlib.Path(__file__).resolve().parents[2] / "native"

_codec: Any = None
_codec_err: str | None = None


class StaleCodecError(RuntimeError):
    """A binary that does not state ``_ABI`` and could not be replaced."""


def _states_abi(so_path: pathlib.Path) -> bool:
    """Whether the binary's bytes hold this module's ABI marker. Read
    from the file, not from the loaded module: a single-phase extension
    cannot be loaded twice from one path in one process, so a stale one
    must be found before it is loaded."""
    marker = b"copycat_codec_abi=%d\0" % _ABI
    return marker in so_path.read_bytes()


def _build_and_load(native_dir: pathlib.Path = _NATIVE_DIR) -> Any:
    src = native_dir / "copycat_codec.c"
    so_path = native_dir / "copycat_codec.so"
    stale = so_path.exists() and not _states_abi(so_path)
    if (stale or not so_path.exists()
            or so_path.stat().st_mtime < src.stat().st_mtime):
        try:
            subprocess.run(
                ["make", "-B", "-C", str(native_dir), "copycat_codec.so"],
                check=True, capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as exc:
            if stale:
                raise StaleCodecError(
                    f"{so_path} predates codec ABI {_ABI} and cannot be "
                    f"rebuilt ({exc}); it is not loaded") from exc
            raise
        if not _states_abi(so_path):
            raise StaleCodecError(
                f"{so_path} was rebuilt from {src} and still does not "
                f"state codec ABI {_ABI}")
    loader = importlib.machinery.ExtensionFileLoader(
        "copycat_codec", str(so_path))
    spec = importlib.util.spec_from_loader("copycat_codec", loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    if getattr(mod, "ABI", None) != _ABI:
        raise StaleCodecError(
            f"{so_path} loaded with ABI {getattr(mod, 'ABI', None)}, "
            f"need {_ABI}")
    return mod


def _configure(mod: Any) -> None:
    from .buffer import BufferInput, BufferOutput
    from . import serializer as s

    default = s.Serializer()

    def encode_body(obj: Any) -> bytes:
        _python_bodies.inc()
        buf = BufferOutput()
        obj.write_object(buf, default)
        return buf.to_bytes()

    def decode_body(cls: type, data: bytes, pos: int):
        _python_bodies.inc()
        buf = BufferInput(data)
        buf._pos = pos
        obj = cls.__new__(cls)
        obj.read_object(buf, default)
        return obj, buf._pos

    mod.configure(s._ID_BY_TYPE, s._TYPE_REGISTRY, s._CODEC_FIELDS,
                  encode_body, decode_body, s._CODEC_OPTIONAL,
                  s._CODEC_HEAD)


def codec() -> Any:
    """The configured extension module, or None when unavailable."""
    global _codec, _codec_err
    if _codec is not None or _codec_err is not None:
        return _codec
    try:
        mod = _build_and_load()
        _configure(mod)
        _codec = mod
    except StaleCodecError as exc:  # never in silence: say so, once
        _codec_err = str(exc)
        warnings.warn(f"native codec refused: {exc}", RuntimeWarning,
                      stacklevel=2)
    except Exception as exc:  # toolchain missing — degrade gracefully
        _codec_err = str(exc)
    return _codec


def codec_error() -> str | None:
    return _codec_err
