"""Native (C++) components and their lazy build machinery.

The reference ships its runtime as C++ compiled by bazel
(src/ray/BUILD.bazel); here the native pieces are small, dependency-free
C++ translation units compiled on first use with g++ and cached next to
the source (git-ignored: a fresh checkout builds them). A pure-Python
fallback exists for every native component so the framework still works
where no toolchain is present.
"""

from __future__ import annotations

import fcntl
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))


def build_library(name: str) -> str | None:
    """Compile `<name>.cc` → `lib<name>.so` (cached by mtime). Returns the
    .so path, or None where there is no g++ (the callers' pure-Python
    fallback). A compile that runs and fails raises.

    Head, nodelet and workers of a fresh checkout all get here at once:
    a file lock lets one of them build, and the library appears under
    its final name only complete (`os.replace` of a finished file), so
    no process can load a half-written one."""
    src = os.path.join(_HERE, f"{name}.cc")
    out = os.path.join(_HERE, f"lib{name}.so")

    def fresh() -> bool:
        return os.path.exists(out) and \
            os.path.getmtime(out) >= os.path.getmtime(src)

    if fresh():
        return out
    # the lock belongs to this open file, so it also excludes other
    # threads of this process
    with open(out + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if fresh():  # someone else built it while we waited
            return out
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                 "-o", tmp, src],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, out)
        except FileNotFoundError:
            return None  # no toolchain
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"building {out} failed:\n"
                f"{e.stderr.decode(errors='replace')[-2000:]}") from e
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return out
