"""Execution context: the ``device`` parameter and the thread count.

TPU-native analogue of the reference's ``Context``/``DeviceOrd``
(include/xgboost/context.h:40, src/context.cc:105-155).  The reference parses
``device="cpu"|"cuda[:N]"|"gpu"|"sycl:*"`` and dispatches to a code path; here
compute is dispatched through JAX, and JAX's default device places every
array.  So ``device`` does not move anything: it *asserts* where this process
computes.  ``device="tpu"`` in a process whose JAX found no TPU raises; it
never trains on another platform in silence.  An absent ``device`` asserts
nothing and runs wherever JAX runs.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DEVICE_RE = re.compile(r"^(cpu|tpu|gpu|cuda)(:(\d+))?$")


@dataclasses.dataclass(frozen=True)
class DeviceOrd:
    """A parsed device: ``type`` is 'cpu' or 'tpu', ``ordinal`` indexes
    ``jax.devices(type)``.

    Mirrors DeviceOrd (include/xgboost/context.h:40); 'gpu'/'cuda' are accepted
    and mapped to the accelerator ('tpu') for drop-in compatibility.
    """

    type: str = "cpu"
    ordinal: int = 0

    @staticmethod
    def parse(spec: str) -> "DeviceOrd":
        spec = spec.strip().lower()
        m = _DEVICE_RE.match(spec)
        if m is None:
            raise ValueError(
                f"Invalid device spec: {spec!r}. Expected 'cpu', 'tpu', or 'tpu:<ordinal>'."
            )
        kind = m.group(1)
        if kind in ("gpu", "cuda"):  # accept reference spellings; run on the accelerator
            kind = "tpu"
        ordinal = int(m.group(3) or 0)
        return DeviceOrd(kind, ordinal)

    def __str__(self) -> str:
        return f"{self.type}:{self.ordinal}"

    def jax_device(self):
        """The ``jax.Device`` this names.  Raises, naming what JAX found,
        when there is none such: never another platform's device."""
        import jax

        try:
            devs = jax.devices(self.type)
        except RuntimeError as e:
            raise RuntimeError(
                f"device={str(self)!r} was asked for, but JAX found no "
                f"{self.type!r} platform here: its devices are "
                f"{jax.devices()}") from e
        if self.ordinal >= len(devs):
            raise RuntimeError(
                f"device={str(self)!r} was asked for, but JAX found only "
                f"{len(devs)} {self.type!r} device(s): {devs}")
        return devs[self.ordinal]


@dataclasses.dataclass
class Context:
    """Runtime context threaded through training (reference: include/xgboost/context.h).

    nthread/seed mirror the reference Context fields; ``device`` is None when
    the parameter was not given.
    """

    device: Optional[DeviceOrd] = None
    nthread: int = 0
    seed: int = 0

    @staticmethod
    def create(device: Optional[str] = None, nthread: int = 0,
               seed: int = 0) -> "Context":
        return Context(
            device=None if device is None else DeviceOrd.parse(str(device)),
            nthread=int(nthread), seed=seed)

    def apply_nthread(self) -> int:
        """Push the resolved thread count into the native ParallelFor pools
        (both kernel libraries).  Precedence (docs/native_threading.md):
        explicit ``nthread`` param > ``XGBOOST_TPU_NTHREAD`` env >
        ``os.cpu_count()`` — the reference's nthread/OMP_NUM_THREADS
        resolution (src/common/threading_utils.cc OmpGetNumThreads) with
        the package env var in OMP's seat.  Bitwise-neutral: threaded
        kernels are pinned identical to nthread=1 for every value."""
        from .utils import native

        return native.set_nthread(self.nthread)

    def check_device(self) -> None:
        """Hold the process to the ``device`` parameter: the device it names
        must exist and be the one on which JAX places this process's arrays
        (observed from a fresh array, so ``JAX_PLATFORMS`` and
        ``jax.default_device`` both count)."""
        if self.device is None:
            return
        import jax.numpy as jnp

        want = self.device.jax_device()
        (got,) = jnp.zeros((), jnp.int8).devices()
        if got != want:
            raise RuntimeError(
                f"device={str(self.device)!r} names {want}, but JAX places "
                f"this process's arrays on {got}.  The parameter asserts the "
                f"device and moves nothing: choose it with JAX_PLATFORMS or "
                f"jax.default_device, or leave `device` out to run wherever "
                f"JAX does.")
