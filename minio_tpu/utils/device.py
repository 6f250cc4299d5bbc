"""The one device probe: what the data path runs on, decided once.

Every routing and kernel-flavour decision in the program (codec route,
mesh, Pallas-vs-XLA matmul, hash-kernel unroll, scan plane) reads
``probe()`` instead of asking JAX itself, so a missing or broken chip
has exactly one place to show up — with the backend's own words, not a
bare False. A CPU-only host stays a supported deployment; the boot
banner and one ``device.decline`` event say that it is one and why.

The persistent compile cache is placed here too, before the backend
comes up: ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
itself, nothing is done); otherwise the cache lives at
``<checkout>/.jax_cache``. The path is part of the cache key, so it is
computed from the package's own location — never a temp name.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Optional

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class DataPath:
    """What JAX reports for this process: ``jax.devices()[0].platform``,
    ``.device_kind`` and ``len(jax.devices())`` — or, when no backend
    came up, empty values and the exception text in ``reason``.
    ``reason`` is non-empty exactly when there is no usable
    accelerator."""
    platform: str
    device_kind: str
    count: int
    reason: str

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"


def compile_cache_dir() -> tuple[str, bool]:
    """(directory, placed_by_us): the environment's directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env, False
    return os.path.join(_CHECKOUT, ".jax_cache"), True


_MU = threading.Lock()
_PROBED: Optional[DataPath] = None


def _probe_once() -> DataPath:
    path, ours = compile_cache_dir()
    if ours:
        jax.config.update("jax_compilation_cache_dir", path)
    try:
        devs = jax.devices()
    except Exception as e:  # noqa: BLE001 — boundary: a host whose
        # backend cannot initialise (chip held by another process,
        # libtpu failing to load) still serves from the CPU, and says
        # why in the backend's own words
        return DataPath("", "", 0,
                        f"{type(e).__name__}: {' '.join(str(e).split())}")
    d = devs[0]
    reason = "" if d.platform == "tpu" else \
        f"JAX's default backend is {d.platform!r}"
    return DataPath(d.platform, d.device_kind, len(devs), reason)


def probe() -> DataPath:
    """The process's data path, probed on first call (before the first
    jit: every device entry point reads it) and fixed from then on."""
    global _PROBED
    if _PROBED is None:
        with _MU:
            if _PROBED is None:
                _PROBED = _probe_once()
    return _PROBED
