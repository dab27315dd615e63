# Pallas TPU kernels for the paper-relevant compute hot spots. Each
# subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper, interpret mode on the CPU backend only) and ref.py (pure-jnp
# oracle).
#
#   persistent/        LK work-queue executor megakernel (paper core)
#   flash_attention/   blockwise causal/local/softcap GQA flash
#   decode_attention/  flash-decoding vs long KV caches
#   ssd_scan/          mamba2 SSD chunk kernel
import jax


def default_interpret() -> bool:
    """Interpret-mode default of every kernel wrapper: compiled on the TPU,
    the Pallas interpreter on the CPU backend (tests), and an error on any
    other backend — a kernel never silently runs interpreted on a device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and interpret on the CPU; "
        f"backend {backend!r} is neither")
