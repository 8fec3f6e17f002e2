"""The trace reduction on a small recorded trace with known busy time,
idle gaps and per-operation sums."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH
import xplane_fixture

MS = 1_000_000  # ns


@pytest.fixture()
def recorded(tmp_path):
    path = str(tmp_path / "host.xplane.pb")
    dev0 = {
        "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),     # 10..30
                    ("fusion.2", 25 * MS, 15 * MS),     # 25..40 overlaps
                    ("copy.3", 100 * MS, 5 * MS),       # 100..105
                    ("fusion.1", 400 * MS, 10 * MS)],   # 400..410
        # not operations; two shapes of one jitted function
        "XLA Modules": [("jit_band(123456789)", 0, 300 * MS),
                        ("jit_band(987654321)", 300 * MS, 200 * MS)],
    }
    dev1 = {"XLA Ops": [("fusion.1", 0, 60 * MS)]}
    host = {"python3": [("PjitFunction(band)", 0, 900 * MS)]}
    xplane_fixture.write(path, [
        ("/device:TPU:0", dev0, {}),
        ("/device:TPU:1", dev1, {}),
        ("/host:CPU", host, {}),
        ("Task Environment", {}, {"profile_start_time": 5_000_000_000,
                                  "profile_stop_time": 6_000_000_000}),
    ])
    return path


def test_known_busy_idle_and_sums(recorded):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lib", "trace.py"), recorded],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["window_s"] == pytest.approx(1.0)
    # device 0: 10..40, 100..105, 400..410 = 45 ms; device 1: 60 ms
    assert got["busy_s"] == pytest.approx((0.045 + 0.060) / 2)
    assert got["op_sum_s"] == pytest.approx((0.050 + 0.060) / 2)
    assert got["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    # named by compiled program where the device has that line (device 0),
    # by operation where it has not (device 1)
    ops = dict(got["device_ops"])
    assert ops == {"jit_band": pytest.approx(0.5),
                   "fusion.1": pytest.approx(0.060)}
    assert [name for name, _ in got["idle_gaps"]] == ["unattributed"] * 2
    assert [s for _, s in got["idle_gaps"]] == pytest.approx([0.295, 0.060])
