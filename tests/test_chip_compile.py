"""The main-path programs compile for a TPU v5e at real widths.

Nothing runs: each program is lowered for one chip of a described (not
attached) v5e topology and compiled by the TPU compiler, which refuses
what the chip cannot run — an unaligned block, too much fast memory, a
primitive Mosaic cannot lower — and reports the bytes the program needs.
The topology is described inside a fixture, never while the module is
imported, so every test worker collects the same tests.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

kernel_bench = pytest.importorskip(
    "benchmarks.kernel_bench",
    reason="benchmarks package needs the repo root on sys.path")

from repro.configs.base import TRAIN_4K, get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.stats_boot import _boot_median_ci_block  # noqa: E402

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(lowered, *, kernel: bool):
    compiled = lowered.compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("S, window", [
    (TRAIN_4K.seq_len, None),
    (TRAIN_4K.seq_len, 1024),
    (1000, None),                 # padded to the block size
])
def test_flash_attention_compiles_at_internlm2_widths(one_chip, S, window):
    lm = get_config("internlm2-1.8b")
    dt = jnp.dtype(lm.dtype)
    q = _on(one_chip, (1, S, lm.num_heads, lm.head_dim), dt)
    kv = _on(one_chip, (1, S, lm.num_kv_heads, lm.head_dim), dt)
    _compile(ops.flash_attention.lower(q, kv, kv, causal=True, window=window,
                                       interpret=False), kernel=True)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    s = cfg.ssm
    S, H, dt = TRAIN_4K.seq_len, s.n_heads(cfg.d_model), jnp.dtype(cfg.dtype)
    x = _on(one_chip, (1, S, H, s.head_dim), dt)
    step = _on(one_chip, (1, S, H), jnp.float32)
    a = _on(one_chip, (H,), jnp.float32)
    bc = _on(one_chip, (1, S, s.n_groups, s.d_state), dt)
    _compile(ops.ssd_scan.lower(x, step, a, bc, bc, chunk=s.chunk_size,
                                interpret=False), kernel=True)


def test_device_bootstrap_block_compiles(one_chip):
    block = _on(one_chip, (100, 200), jnp.float32)
    idx = _on(one_chip, (1000, 200), jnp.int32)
    _compile(_boot_median_ci_block.lower(block, idx, lo_idx=4, hi_idx=995),
             kernel=False)


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("name", sorted(kernel_bench._FP_MODULES))
def test_kernel_suite_duet_compiles_at_full_widths(one_chip, name, version):
    spec = {s.name: s for s in kernel_bench.duet_specs()}[name]
    args = [_on(one_chip, s.shape, s.dtype) for s in spec.shapes]
    _compile(jax.jit(getattr(spec, version)).lower(*args), kernel=False)
