"""Shared build-and-load policy for the native libraries in ``native/``.

One place owns the rules — invoke make incrementally on every first load
(a no-op when fresh, guarantees .cpp edits are picked up; a stale .so
would silently serve old native code otherwise).  A make that runs and
fails is an error, whether or not an older .so is lying around: what
loads is built from the sources in ``native/``.  Only a machine with no
``make`` and no built library degrades to ``None`` (callers keep their
pure-Python path).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

__all__ = ["load_native_lib", "NATIVE_DIR"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")


_MAKE_RAN = False


# The lock lives OUTSIDE native/build: `make clean` rm -rf's build/, and
# unlinking a held lock file would let a second process lock a fresh inode
# and compile concurrently — the exact race the lock prevents.
_LOCK_PATH = os.path.join(NATIVE_DIR, ".make.lock")


def _run_make_locked() -> None:
    """make under an exclusive file lock: concurrent processes (the
    multi-host workers, parallel test runs) must not race two compilers
    onto the same .so — the loser would dlopen a half-written library."""
    import fcntl

    with open(_LOCK_PATH, "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        try:
            subprocess.run(["make", "-C", NATIVE_DIR], check=True,
                           capture_output=True, timeout=120)
        except subprocess.CalledProcessError as exc:
            raise RuntimeError(
                f"native build failed (make -C {NATIVE_DIR}, exit "
                f"{exc.returncode}):\n"
                f"{exc.stderr.decode(errors='replace')[-2000:]}") from exc
        finally:
            fcntl.flock(lock_file, fcntl.LOCK_UN)


def load_native_lib(lib_name: str) -> Optional[ctypes.CDLL]:
    """Build (if needed) and load ``native/build/lib{lib_name}.so``;
    ``None`` means no native path (caller falls back).  Callers cache the
    result and declare their own symbol signatures.  One ``make all``
    builds every target, so the subprocess runs once per process no
    matter how many libraries load."""
    global _MAKE_RAN
    so_path = os.path.join(NATIVE_DIR, "build", f"lib{lib_name}.so")
    if not _MAKE_RAN and os.path.exists(os.path.join(NATIVE_DIR,
                                                     "Makefile")):
        _MAKE_RAN = True
        try:
            _run_make_locked()
        except FileNotFoundError:   # no make on this machine
            pass
    try:
        # shared lock around dlopen: a concurrent process rebuilding the
        # library (exclusive lock) writes -o straight onto this path, and
        # loading mid-write would tear the mapping
        import fcntl

        with open(_LOCK_PATH, "w") as lock_file:
            fcntl.flock(lock_file, fcntl.LOCK_SH)
            try:
                return ctypes.CDLL(so_path)
            finally:
                fcntl.flock(lock_file, fcntl.LOCK_UN)
    except OSError:
        return None
