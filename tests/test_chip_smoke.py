"""chip_smoke.py's pieces that need no chip: its FP table is what `as`
produces for the source beside each entry, and its comparisons compare
what they claim to.  The script itself is rehearsed by hand
(`python chip_smoke.py --rehearse-cpu`) and proven by the chip run."""

import pytest

import chip_smoke
from tests.asmhelper import assemble


@pytest.mark.parametrize("name,hosted,source,code,regs", chip_smoke.FP_CASES,
                         ids=[case[0] for case in chip_smoke.FP_CASES])
def test_fp_case_bytes_match_source(name, hosted, source, code, regs):
    assert assemble(source).hex() == code


def test_fp_cases_cover_both_sides_of_fp_on_host():
    hosted = [case[1] for case in chip_smoke.FP_CASES]
    assert len(hosted) >= 12 and any(hosted) and not all(hosted)


def test_stable_line_drops_only_the_clock_fields():
    a = ("#64 cov: 53 corp: 8 exec/s: 1.9 crash: 14 timeout: 0 cr3: 0 "
         "uptime: 34.5s")
    b = ("#64 cov: 53 corp: 8 exec/s: 83.1 crash: 14 timeout: 0 cr3: 0 "
         "uptime: 0.4s")
    assert chip_smoke.stable(a) == chip_smoke.stable(b) == (
        "#64 cov: 53 corp: 8 crash: 14 timeout: 0 cr3: 0")
    assert chip_smoke.stable(a.replace("corp: 8", "corp: 9")) \
        != chip_smoke.stable(a)


def test_dir_digest_sees_names_and_contents(tmp_path):
    (tmp_path / "a").write_bytes(b"x")
    one = chip_smoke.dir_digest(tmp_path)
    (tmp_path / "a").write_bytes(b"y")
    two = chip_smoke.dir_digest(tmp_path)
    (tmp_path / "a").rename(tmp_path / "b")
    assert len({one, two, chip_smoke.dir_digest(tmp_path)}) == 3


def test_same_raises_on_a_differing_digest():
    a = {"tag": "a", "line": "#1", "outputs": "d1", "crashes": "c"}
    chip_smoke.same(a, dict(a, tag="b"), "equal")
    with pytest.raises(AssertionError, match="outputs differs"):
        chip_smoke.same(a, dict(a, tag="b", outputs="d2"), "unequal")
