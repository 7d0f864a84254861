from repro.kernels.ops import flash_attention, ssd_scan  # noqa: F401
from repro.kernels.stats_boot import bootstrap_median_ci_batch_jax  # noqa: F401
