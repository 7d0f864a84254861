"""The work counts and peaks behind the roofline shares, and the widths
the configuration files give the program's duets."""
import jax
import pytest

from bench.entries import Entries, EntryError

kernel_bench = pytest.importorskip(
    "benchmarks.kernel_bench",
    reason="benchmarks package needs the repo root on sys.path")

ENTRIES = Entries()
CONFIG_OF = {d: c["name"] for c in ENTRIES.spec["configs"]
             for d in ENTRIES.config(c["name"])["deployment"]["benchmarks"]}

#: (flops, bytes) of one call at the configurations' widths, by hand.
FULL_WIDTHS = {
    # causal pairs 4096 * 4097 / 2, two products of 2 * 128 flops each,
    # for 16 heads; q and o of 16 heads, k and v of 8, in bf16
    "attention_dot_vs_chunked": (4 * 16 * 128 * (4096 * 4097 // 2),
                                 (2 * 16 + 2 * 8) * 4096 * 128 * 2),
    # 4 flops an element; x and y moved once, w once, in bf16
    "rmsnorm_fused_vs_unfused": (4 * 4096 * 2048,
                                 (2 * 4096 * 2048 + 2048) * 2),
    # 5 P N per head and step; x, y, B, C in bf16, dt and A in f32
    "ssd_recurrence_vs_chunked": (5 * 4096 * 64 * 64 * 128,
                                  2 * 4096 * 64 * 64 * 2 + 4096 * 64 * 4
                                  + 64 * 4 + 2 * 4096 * 128 * 2),
}


def specs(small):
    return {s.name: s for s in kernel_bench.duet_specs(small=small)}


@pytest.mark.parametrize("duet", sorted(FULL_WIDTHS))
def test_work_at_the_configured_widths(duet):
    work = ENTRIES.work(duet)
    cfg = ENTRIES.config(CONFIG_OF[duet])
    assert work.work(work.shapes(cfg)) == FULL_WIDTHS[duet]


@pytest.mark.parametrize("duet", sorted(FULL_WIDTHS))
def test_configured_widths_are_the_program_duets_arguments(duet):
    work = ENTRIES.work(duet)
    cfg = ENTRIES.config(CONFIG_OF[duet])
    program = [(tuple(s.shape), str(s.dtype)) for s in specs(False)[duet]
               .shapes]
    assert [(tuple(s), str(jax.numpy.dtype(t)))
            for s, t in work.shapes(cfg)] == program


@pytest.mark.parametrize("duet", sorted(FULL_WIDTHS))
def test_work_is_the_same_whichever_version_is_counted(duet):
    """Both versions take the same arguments, and the count reads only
    their shapes: whichever version's call is counted, the work is the
    function's."""
    spec = specs(True)[duet]
    work = ENTRIES.work(duet)
    args = spec.make_args()
    for fn in (spec.v1, spec.v2):
        traced = jax.jit(fn).trace(*args)
        seen = [(a.shape, a.dtype) for a in traced.args_info[0]]
        assert [tuple(s) for s, _ in seen] == [a.shape for a in args]
        assert work.work(seen) == work.work([(a.shape, a.dtype)
                                             for a in args])
    flops, moved = work.work([(a.shape, a.dtype) for a in args])
    assert flops > 0 and moved > 0


def test_peaks_are_keyed_by_device_kind_with_their_source():
    v5e = ENTRIES.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(EntryError):
        ENTRIES.peaks("cpu")
