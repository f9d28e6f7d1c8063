"""``chip_smoke.py`` on the CPU: its phases reproduce the golden the chip
is held to, and its entry point refuses to run without a TPU."""
import pytest

import chip_smoke

PHASES = {name: (fn, section) for name, fn, section in chip_smoke.ONE_CHIP}


@pytest.fixture(scope="module")
def golden():
    return chip_smoke.load_golden()


@pytest.mark.parametrize("name", list(PHASES))
def test_phase_matches_cpu_golden(name, golden):
    fn, section = PHASES[name]
    rows, _ = fn()
    assert chip_smoke.mismatches(chip_smoke.phase_records(rows),
                                 golden[section]) == []


def test_golden_covers_every_phase(golden):
    sections = {s for _, _, s in chip_smoke.ONE_CHIP + chip_smoke.FOUR_CHIP}
    assert sections == set(golden)


def test_mismatch_names_the_lane_and_field(golden):
    want = golden["chase"]
    (label, rec), = want.items()
    assert chip_smoke.mismatches(dict(want), want) == []
    bad = chip_smoke.mismatches({label: dict(rec, cycles=rec["cycles"] + 1)},
                                want)
    assert len(bad) == 1 and label in bad[0] and "cycles" in bad[0]
    assert chip_smoke.mismatches({}, want) == [f"{label}: missing from run"]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_main_refuses_without_tpu(argv, monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(chip_smoke, "run_phase",
                        lambda *a, **k: ran.append(a))
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert not ran
    assert '"ok"' not in out
