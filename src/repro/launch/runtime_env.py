"""Host runtime environment preset (ROADMAP "host runtime hardening").

The multi-process launcher spawns worker interpreters; each one pays the
host-side costs the big JAX training launchers all patch over the same
way (HomebrewNLP/olmax run.sh, MaxText MultiHostJob -- SNIPPETS §1-3):

  * glibc malloc fragments the large transient host buffers the finalize
    stage churns through -- preload tcmalloc when the host has it;
  * tcmalloc then logs every "large alloc" over ~1 GB to stderr, which
    garbles benchmark CSV output -- raise the report threshold;
  * TF/XLA C++ logging defaults to chatty INFO on workers -- silence it;
  * the CPU emulation path needs ``--xla_force_host_platform_device_count``
    set *before* jax imports, so it must travel via the child environment.

Everything here except ``enable_compile_cache`` is a pure dict-in/dict-out
helper: nothing touches ``os.environ`` of the calling process, and
importing this module never imports jax (launchers build child
environments long before jax exists in the child).

``enable_compile_cache`` is the one in-process setting: it points JAX's
persistent compilation cache at a directory that can be placed from
outside (``JAX_COMPILATION_CACHE_DIR``) and is otherwise fixed inside
the checkout, so a second run of the same program finds its compiled
kernels again.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

# Common soname locations across distro families; first hit wins.  The
# plain .so names cover toolchain images that ship only the -dev links.
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so",
)

# ~60 GB, the olmax value: effectively "never report" without disabling
# the accounting entirely.
TCMALLOC_REPORT_THRESHOLD = "60000000000"


# Persistent compilation cache: where JAX keeps compiled executables.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives at the fixed
    ``<root>/.jax_cache`` (``root`` is the checkout the caller runs
    from): a path built from a temp name, a pid or the time would never
    be found again by the next run.  Call before the first compile.
    """
    path = os.environ.get(COMPILE_CACHE_ENV)
    if path:
        return path
    import jax
    path = os.path.join(os.path.abspath(root), COMPILE_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def find_tcmalloc(candidates=TCMALLOC_CANDIDATES) -> Optional[str]:
    """First present tcmalloc soname, or None (glibc malloc stays)."""
    for path in candidates:
        if os.path.exists(path):
            return path
    return None


def merge_xla_flags(existing: Optional[str], flags: List[str]) -> str:
    """Append XLA flags to an existing XLA_FLAGS value, dropping any
    duplicate ``--flag=...`` the caller is overriding (last write wins,
    matching XLA's own parse order would keep the first -- so we remove
    the stale copy instead of relying on it)."""
    keep = []
    new_keys = {f.split("=", 1)[0] for f in flags}
    for tok in (existing or "").split():
        if tok.split("=", 1)[0] not in new_keys:
            keep.append(tok)
    return " ".join(keep + list(flags)).strip()


def runtime_env(base: Optional[Dict[str, str]] = None, *,
                host_device_count: Optional[int] = None,
                tcmalloc: bool = True,
                quiet_logs: bool = True) -> Dict[str, str]:
    """Build a child-process environment with the runtime preset applied.

    ``base`` defaults to a copy of ``os.environ``; the result is always a
    new dict.  ``host_device_count`` adds the CPU-emulation XLA flag
    (``--xla_force_host_platform_device_count=K``), which only has an
    effect when set before the child imports jax -- which is exactly why
    it lives in the environment and not in code.
    """
    env = dict(os.environ if base is None else base)
    if tcmalloc:
        lib = find_tcmalloc()
        if lib is not None:
            pre = env.get("LD_PRELOAD", "")
            if lib not in pre.split(":"):
                env["LD_PRELOAD"] = f"{pre}:{lib}".strip(":")
            env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                           TCMALLOC_REPORT_THRESHOLD)
    if quiet_logs:
        env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    if host_device_count is not None:
        env["XLA_FLAGS"] = merge_xla_flags(
            env.get("XLA_FLAGS"),
            [f"--xla_force_host_platform_device_count={host_device_count}"])
    return env


__all__ = ["enable_compile_cache", "find_tcmalloc", "merge_xla_flags",
           "runtime_env", "COMPILE_CACHE_ENV", "COMPILE_CACHE_DIRNAME",
           "TCMALLOC_CANDIDATES", "TCMALLOC_REPORT_THRESHOLD"]
