"""Roofline helpers: HLO collective parsing + term math."""
from repro.launch.roofline import (PEAKS, V5E, collective_bytes,
                                   roofline_terms, _shape_bytes)

HLO = """
ENTRY %main {
  %ag = bf16[8,128,256]{2,1,0} all-gather(%x), replica_groups=...
  %ar = f32[1024,1024]{1,0} all-reduce(%y), to_apply=%add
  %ars = f32[64,64]{1,0} all-reduce-start(%z)
  %rs = bf16[2,4]{1,0} reduce-scatter(%w)
  %a2a = bf16[16,8,320,4096]{3,2,1,0} all-to-all(%v)
  %cp = u32[128]{0} collective-permute(%u)
  %dot = f32[128,128]{1,0} dot(%a, %b)
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128,256]") == 8 * 128 * 256 * 2
    assert _shape_bytes("f32[1024,1024]") == 1024 * 1024 * 4
    assert _shape_bytes("(f32[2,2], s32[3])") == 16 + 12


def test_collective_bytes_parses_all_kinds():
    out = collective_bytes(HLO)
    assert out["all-gather"] == 8 * 128 * 256 * 2
    # all-reduce counted twice (ring reduce+broadcast), includes -start
    assert out["all-reduce"] == 2 * (1024 * 1024 * 4 + 64 * 64 * 4)
    assert out["reduce-scatter"] == 2 * 4 * 2
    assert out["all-to-all"] == 16 * 8 * 320 * 4096 * 2
    assert out["collective-permute"] == 128 * 4
    assert out["count"] == 6
    assert out["total"] == sum(out[k] for k in (
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute"))


def test_roofline_terms_dominance():
    hw = V5E
    t = roofline_terms(197e12, 0.0, 0.0, hw)   # 1s compute, nothing else
    assert t["dominant"] == "compute" and abs(t["t_compute_s"] - 1.0) < 1e-9
    assert t["roofline_fraction"] == 1.0
    t = roofline_terms(197e10, 819e9, 0.0, hw)  # memory 1s vs compute 10ms
    assert t["dominant"] == "memory"
    assert abs(t["roofline_fraction"] - 0.01) < 1e-6
    t = roofline_terms(0.0, 0.0, 50e9, hw)
    assert t["dominant"] == "collective"


def test_model_flops_conventions():
    from repro.launch.roofline import model_flops, active_params
    from repro import configs as C
    cfg = C.get_config("olmoe-1b-7b")
    act, tot = active_params(cfg)
    assert act < tot  # MoE: only top-k experts active
    assert model_flops(cfg, "train", 2, 128) == 6.0 * act * 256
    assert model_flops(cfg, "decode", 4, 999) == 2.0 * act * 4


def test_kernelstats_roofline_agrees_with_roofline_terms():
    """The live roofline repro.obs.kernelstats builds must use the same
    compute/memory term math as the static launch-planning model."""
    from repro.obs import KernelStats
    ks = KernelStats()
    ks.record("coded_project", m=256, d=64, k=64)
    hw = V5E
    row = ks.roofline_table(hw)["coded_project"]
    terms = roofline_terms(row["flops"], row["hbm_bytes"], 0.0, hw)
    assert row["t_compute_s"] == terms["t_compute_s"]
    assert row["t_memory_s"] == terms["t_memory_s"]
    assert row["bound"] == terms["dominant"]


def test_peaks_table_is_keyed_by_device_kind():
    """The v5e entry carries the published figures; a kind with no
    entry has none, rather than another chip's."""
    assert PEAKS["TPU v5 lite"] is V5E
    assert (V5E.peak_flops, V5E.peak_int8_ops, V5E.hbm_bw) == (
        197e12, 393e12, 819e9)
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        assert PEAKS.get(kind) is None


def test_kernelstats_roofline_empty_for_unlisted_device():
    """Off the table (the CPU here), the live roofline models nothing."""
    import jax
    from repro.obs import KernelStats
    assert jax.devices()[0].device_kind not in PEAKS
    ks = KernelStats()
    ks.record("coded_project", m=256, d=64, k=64)
    assert ks.roofline_table() == {}
    assert set(ks.roofline_table(V5E)) == {"coded_project"}
