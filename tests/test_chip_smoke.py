"""CPU rehearsal of chip_smoke.py's control flow at a tiny size: the
engine phase and the 4-chip phase run their tick schedules (storm with
paging, steady walk, despawn + teleport) through the Pallas interpreter
and compare with the jnp reference; ``main`` still refuses a CPU device.
The served phase is covered on CPU by tests/test_stress.py."""

import jax

import chip_smoke
from goworld_tpu.ops import NeighborParams

# 64 inline events a side (a chip's window), so the enter storm pages.
PARAMS = NeighborParams(capacity=256, cell_size=100.0, grid_x=16, grid_z=4,
                        space_slots=2, cell_capacity=64, max_events=64)


def test_engine_phase_rehearsal():
    rec = chip_smoke.engine_phase(PARAMS, backend="pallas_interpret",
                                  n_steady=2)
    assert rec["ok"] and rec["backend"] == "pallas_interpret"
    ticks = rec["ticks"]
    assert [t["kind"] for t in ticks] == [
        "storm", "steady", "steady", "despawn_teleport"]
    assert ticks[0]["enters"] > PARAMS.max_events
    assert ticks[-1]["leaves"] > 0


def test_chips4_phase_rehearsal():
    rec = chip_smoke.chips4_phase(
        PARAMS, jax.devices("cpu")[:4], backend="pallas_interpret",
        n_steady=2, prewarm_fallback=False)
    assert rec["ok"] and rec["phase"] == "chips4"
    assert rec["drain_inline"] == PARAMS.max_events  # a chip's window
    assert rec["state_shards_on_distinct_devices"] == 4
    assert rec["aoi_link_bytes_total_halo"] > 0
    assert [t["kind"] for t in rec["ticks"]][-1] == "despawn_teleport"
    # The strip kernel (in-kernel drain) ran, not only the fallback.
    assert any(t["mode"] == "spatial" for t in rec["ticks"][1:])


def test_main_refuses_a_cpu_device(capsys):
    assert chip_smoke.main(["--phase", "engine"]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err
