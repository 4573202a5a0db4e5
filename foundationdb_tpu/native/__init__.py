"""Native (C++) components, loaded via ctypes with build-on-first-use.

The reference keeps its hot CPU paths in hand-tuned C++ (fdbserver/SkipList.cpp,
flow's Arena); here the C++ side is the CPU-baseline conflict engine and the
batch key packer. Libraries are compiled once into native/_build/ with g++
(no pip deps), then dlopened.

A built library is reused only when the stamp beside it matches a hash of
the source, the compiler flags and this host's CPU model: the flags include
-march=native, so a .so copied from another machine may hold instructions
this CPU lacks. Builds go to a temporary name and are renamed into place,
so role processes booting together never load a half-written file.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-march=native")
_LOCK = threading.Lock()
_LIBS: dict[str, ctypes.CDLL] = {}


def _cpu_model() -> str:
    """What -march=native resolves against: the CPU's model and feature
    flags where /proc/cpuinfo has them, the machine type otherwise."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return platform.machine()
    picked = {}
    for line in lines:
        key = line.split(":", 1)[0].strip()
        if key in ("model name", "flags", "Features") and key not in picked:
            picked[key] = line
    return "\n".join(picked.values()) or platform.machine()


def _stamp(src: str) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_model().encode())
    return h.hexdigest()


def _read(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def load_library(name: str) -> ctypes.CDLL:
    """Compile (if stale) and load native/<name>.cpp as lib<name>.so.

    Raises RuntimeError when g++ is missing or the compile fails: every
    caller needs the library it asked for."""
    with _LOCK:
        if name in _LIBS:
            return _LIBS[name]
        src = os.path.join(_DIR, f"{name}.cpp")
        out = os.path.join(_BUILD, f"lib{name}.so")
        stamp_path = out + ".stamp"
        want = _stamp(src)
        if not os.path.exists(out) or _read(stamp_path) != want:
            os.makedirs(_BUILD, exist_ok=True)
            tmp = f"{out}.{os.getpid()}.tmp"
            try:
                subprocess.run(["g++", *_FLAGS, src, "-o", tmp], check=True,
                               capture_output=True, text=True)
                # Library first, stamp second: a reader that sees the new
                # stamp always finds the library it describes.
                os.replace(tmp, out)
                with open(tmp, "w") as f:
                    f.write(want)
                os.replace(tmp, stamp_path)
            except FileNotFoundError as e:
                raise RuntimeError(
                    f"building native/{name}.cpp needs g++ on PATH") from e
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"g++ failed on native/{name}.cpp:\n{e.stderr}") from e
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        lib = ctypes.CDLL(out)
        _LIBS[name] = lib
        return lib
