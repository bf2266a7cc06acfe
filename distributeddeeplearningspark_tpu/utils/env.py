"""Environment/platform plumbing: process identity, the one platform
predicate, the CPU-rehearsal device count, and the compile-cache location.
"""

from __future__ import annotations

import os

#: Where the persistent XLA compilation cache goes when the environment does
#: not place it: one fixed directory inside the checkout, resolved from this
#: package's own location (the path must not move between runs, or nothing
#: ever hits).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def process_identity() -> tuple[int, int]:
    """This host's (process index, process count) from the ``DLS_*`` env
    contract — the same variables the supervisor exports and ``Session``
    consumes (``DLS_PROCESS_ID`` / ``DLS_NUM_PROCESSES``).

    Deliberately env-only, never ``jax.process_index()``: the telemetry
    writer stamps every event with this identity and must work in processes
    that never initialize jax (the supervisor, a crashed worker's last gasp)
    and on boxes without jax at all (``dlstatus`` on a copied-out run
    directory). A malformed value degrades to the single-process identity
    rather than poisoning the event stream.
    """
    try:
        index = int(os.environ.get("DLS_PROCESS_ID", "0"))
    except ValueError:
        index = 0
    try:
        count = int(os.environ.get("DLS_NUM_PROCESSES", "1"))
    except ValueError:
        count = 1
    # a contract violation (id >= count) still yields a usable identity
    return max(0, index), max(1, count, index + 1)


def on_tpu() -> bool:
    """THE platform predicate: is this process's default backend a TPU?

    Every kernel router and Mosaic-vs-interpret decision asks here, so there
    is exactly one spelling of "the chip" in the tree.
    """
    import jax

    return jax.default_backend() == "tpu"


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Resolve a Pallas kernel's ``interpret`` argument against the platform.

    ``None`` → Mosaic-compiled on a TPU, interpreted elsewhere (what the CPU
    tests get). An interpreted kernel on a TPU process is an error: it would
    be a kernel quietly giving way to a Python walk of its grid.
    """
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError(
            "interpret=True on a TPU platform: Pallas kernels compile through "
            "Mosaic there; interpret mode is for CPU tests only")
    return bool(interpret)


def ensure_cpu_devices(n: int | None) -> None:
    """CPU-rehearsal convenience: when the process is held to the host
    platform (``JAX_PLATFORMS=cpu``) and ``XLA_FLAGS`` names no device count,
    ask jax for ``n`` CPU devices so ``local[N]`` / ``dryrun_multichip(N)``
    get their N-way mesh without extra flags. Does nothing on an accelerator
    platform, and nothing once a backend is live (the caller's own
    device-count check then reports the shortfall).
    """
    if not n or n <= 1:
        return
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        return
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already initialized


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in use.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    function touches nothing. Otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Call before the first compile;
    ``Session`` creation does, and so does every entry point that compiles
    without a ``Session``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
