"""The LGBM_TPU_* environment variables the package reads are exactly
the rows of ONE documented table (docs/COMPILE_CACHE.md, "Environment
variables"): a new variable cannot arrive undocumented, and a row
cannot outlive its last reader."""
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = re.compile(r"LGBM_TPU_[A-Z_0-9]+")


def test_env_variables_match_the_documented_table():
    read = set()
    for path in (ROOT / "lightgbm_tpu").rglob("*.py"):
        read.update(NAME.findall(path.read_text()))
    doc = (ROOT / "docs" / "COMPILE_CACHE.md").read_text()
    section = doc.split("## Environment variables", 1)[1].split("\n## ", 1)[0]
    rows = [line.split("|")[1:5] for line in section.splitlines()
            if line.startswith("| `LGBM_TPU_")]
    table = [NAME.fullmatch(cells[0].strip().strip("`")).group(0)
             for cells in rows]
    assert len(table) == len(set(table)), "a variable is listed twice"
    assert set(table) == read, (sorted(read - set(table)),
                                sorted(set(table) - read))
    for name, (_, default, who, meaning) in zip(table, rows):
        assert default.strip() and who.strip() and meaning.strip(), name
