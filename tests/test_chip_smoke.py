"""`chip_smoke.py` off the chip: its bodies at tiny size on the CPU (the
chip run is the driver's), and its refusal to report without a TPU."""

import json

import chip_smoke


def test_smoke_body_passes_on_cpu_at_tiny_size(tmp_path):
    rec = chip_smoke.run_smoke(
        str(tmp_path), 20000, 5000, 1000, num_buckets=8, kernel_widths=chip_smoke.SMALL_WIDTHS
    )
    assert rec["q3_groups"] > 0 and rec["q14_groups"] == 25
    assert rec["lookups"] == chip_smoke.N_LOOKUPS and rec["lookup_rows"] > 0
    assert rec["kernels"]["checked"] == [
        "pallas.sort 8x1024",
        "pallas.sort_comp 8x1024",
        "pallas.sort_codes 8x1024",
        "pallas.probe 8x256x512",
        "pallas.probe_packed 8x256x512",
    ]
    assert {"datagen", "build_liIdx", "q3_indexed", "lookups_indexed"} <= set(rec["phase_s"])
    json.dumps(rec, default=str)


def test_mesh_body_matches_single_device_on_virtual_mesh(tmp_path):
    rec = chip_smoke.run_mesh(str(tmp_path), 80000, 70000, 1000, num_buckets=8)
    assert rec["devices"] == 8 and rec["index_files"] > 0
    assert len(rec["mesh_bytes_per_device"]) == 8


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_gate_widths_are_admitted_by_the_gates():
    """The chip's kernel checks run at shapes the dispatch gates admit, and
    at their widest: the sort cap limit, the probes' ops budget."""
    from hyperspace_tpu.ops import pallas_probe as pp
    from hyperspace_tpu.ops import pallas_sort as ps

    w = chip_smoke.GATE_WIDTHS
    assert all(ps.shape_supported(B, cap) and cap == ps._MAX_CAP for B, cap in w["sort"])
    for B, cap_l, cap_r in w["probe"]:
        assert pp.shape_supported(B, cap_l, cap_r) and B * cap_l * cap_r <= pp._AUTO_MAX_OPS
    for B, cap_l, cap_r in w["probe_packed"]:
        assert pp.packed_shape_supported(B, cap_l, cap_r, 4)
        assert B * cap_l * cap_r == pp._AUTO_MAX_OPS
