"""``chip_smoke.py`` on the CPU at a small size: it must run every phase,
find nothing wrong with the answers, say plainly that no chip ran them, and
end on exactly the line the chip check reads."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ["generate", "load", "compact", "membership", "verify", "serve",
          "reference", "summary"]


@pytest.fixture(scope="module")
def smoke():
    """One run of the script, shared by the cases below — on one plain
    CPU device (conftest's 8 virtual devices are for the mesh tests)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--rows", "20000"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600,
    )


def test_last_stdout_line_has_exactly_the_contract_shape(smoke):
    last = json.loads(smoke.stdout.splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert smoke.stdout.endswith("\n")
    assert not smoke.stdout.endswith("\n\n")


def test_cpu_run_is_not_ok_and_says_why(smoke):
    last = json.loads(smoke.stdout.splitlines()[-1])
    assert last == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert smoke.returncode != 0
    assert "not a tpu" in smoke.stderr


def test_every_phase_ran_clean_and_hid_nothing(smoke):
    """Neither dies early nor hides the CPU: all phases present, 0
    mismatches against generator/oracle/twins, and the device-evidence
    fields read false/0 — which is what fails the run."""
    lines = [json.loads(text) for text in smoke.stdout.splitlines()]
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == PHASES
    assert phases["load"]["rows_stored"] == phases["load"]["rows_expected"]
    assert phases["load"]["native"] is True
    assert phases["load"]["kernel"] == "jnp"
    assert phases["membership"]["duplicates"] \
        == phases["membership"]["duplicates_expected"] > 0
    assert phases["membership"]["device_lookup"]["device_probes"] == 0
    assert "cpu backend" in phases["membership"]["kept_on_host_because"]
    assert phases["verify"]["oracle_sampled"] >= 4096
    assert phases["serve"]["point"] >= 300
    assert phases["serve"]["bulk_ids"] >= 3 * 4096
    assert phases["serve"]["intervals"] >= 256
    assert phases["serve"]["residency_uploads"] == 0
    assert phases["serve"]["exit_code"] == 0
    assert phases["reference"]["compared"] == phases["serve"]["requests"]
    for name in ("verify", "serve", "reference"):
        assert phases[name]["mismatches"] == 0
    for name in ("load", "membership", "serve", "reference"):
        assert phases[name]["device"]["platform"] == "cpu"
    gates = phases["summary"]["gates"]
    assert not any(gates[g] for g in (
        "kernel_pallas", "packed_transport", "device_probe",
        "residency_uploads", "resident_bytes",
    ))
    assert gates["no_breaker_trips"] is True


def test_stdout_carries_phase_lines_only(smoke):
    """One JSON object per phase plus the last line — no child's banner,
    summary or drain message reaches the script's stdout."""
    lines = smoke.stdout.splitlines()
    assert len(lines) == len(PHASES) + 1
    for text in lines[:-1]:
        assert "phase" in json.loads(text)
    assert "phase" not in json.loads(lines[-1])


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The script without the program around it proves nothing: non-zero
    exit, nothing on stdout."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(os.path.join(ROOT, "chip_smoke.py"), "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, str(alone)], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs the repository" in out.stderr
