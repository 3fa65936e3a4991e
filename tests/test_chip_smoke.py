"""``chip_smoke.py``'s phases at toy size on the CPU.

The script's entry point refuses to run without a TPU; its phases are
plain functions, exercised here so every change runs the smoke's code:
the one-chip training + fused-sampler + row-gather checks in process (a
one-device mesh), the four-chip scheme comparison on four placeholder
CPU devices in a subprocess.
"""
import os
import sys
import textwrap

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(nodes=3000, avg_degree=8, batch=16)


def test_entry_point_needs_a_tpu(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phase_toy_size():
    lines = []
    out = chip_smoke.one_chip(steps=2, gather_rows=500, gather_ids=2000,
                              say=lines.append, **TOY)
    assert len(out["losses"]) == 2
    assert out["rounds"] == 2                      # hybrid: 2 feature rounds
    assert any("bit-identical to reference" in x for x in lines)
    assert any("cut: nodes 2,500,000 -> 3,000" in x for x in lines)


def test_four_chip_phase_toy_size(subproc):
    subproc.run_code(textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        runs = chip_smoke.four_chips(steps=2, **{TOY!r})
        assert runs["vanilla"] == runs["hybrid+fused"]
        print("FOUR_CHIP_PHASE_OK")
    """), expect="FOUR_CHIP_PHASE_OK")
