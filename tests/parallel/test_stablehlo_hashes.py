"""``tools/stablehlo_hashes.py`` on the two smallest cells at their ``tiny``
sizes, lowered and compiled for the described chip: what a PR that says it
changes no program runs at the real sizes.  Nothing here is a time.
"""

import json

from tools import stablehlo_hashes

CELLS = ["pythia-1.4b-widths.decode-1k-128", "olmoe-1b-7b.decode-1k-128"]


def test_two_cells_tiny_programs_hash_and_compile(chip, for_the_chip,
                                                  tmp_path):
    def run(path):
        rows = list(stablehlo_hashes.rows(CELLS, chip, tiny=True,
                                          compiled=True))
        path.write_text("".join(json.dumps(row) + "\n" for row in rows))
        return rows

    rows = run(tmp_path / "before.jsonl")
    assert [(r["cell"], r["program"]) for r in rows] == [
        (cell, name) for cell in CELLS
        for name in ("decode_first", "decode_full")]
    for row in rows:
        assert len(row["stablehlo_sha256"]) == 64
        assert set(row["memory"]) == set(stablehlo_hashes.MEMORY)
        assert row["memory"]["argument_size_in_bytes"] > 0
        # tuples' instructions are counted too, and no type is an opcode
        assert row["opcodes"]["fusion"] and row["opcodes"]["tuple"]
        assert not any(op.startswith(("f32", "bf16", "s32"))
                       for op in row["opcodes"])
    assert len({r["stablehlo_sha256"] for r in rows}) == 4
    # the routed cell's kernel is in its program, and blanked in what is
    # hashed: a second lowering gives the same lines
    assert rows[2]["opcodes"]["custom-call"] > rows[0]["opcodes"]["custom-call"]
    again = run(tmp_path / "after.jsonl")
    assert again == rows
    assert stablehlo_hashes.diff(tmp_path / "before.jsonl",
                                 tmp_path / "after.jsonl") == []
    assert stablehlo_hashes.main(["--diff", str(tmp_path / "before.jsonl"),
                                  str(tmp_path / "after.jsonl")]) == 0


def test_a_difference_is_named_by_cell_program_and_field(tmp_path, capsys):
    row = {"cell": "c", "program": "p", "stablehlo_sha256": "a" * 64,
           "memory": {"temp_size_in_bytes": 8}, "opcodes": {"add": 2}}
    other = {**row, "stablehlo_sha256": "b" * 64,
             "opcodes": {"add": 1, "copy": 1}}
    before, after = tmp_path / "b.jsonl", tmp_path / "a.jsonl"
    before.write_text(json.dumps(row) + "\n")
    after.write_text(json.dumps(other) + "\n" + json.dumps(
        {**row, "program": "q"}) + "\n")
    assert stablehlo_hashes.diff(before, after) == [
        ("c", "p", "opcodes.add", 2, 1), ("c", "p", "opcodes.copy", None, 1),
        ("c", "p", "stablehlo_sha256", "a" * 64, "b" * 64),
        ("c", "q", "present", False, True)]
    assert stablehlo_hashes.main(["--diff", str(before), str(after)]) == 1
    assert "4 differences" in capsys.readouterr().out


def test_a_kernels_serialized_module_is_blanked_and_nothing_else():
    text = ('%0 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config '
            '= "{\\"body\\": \\"TUzvUgFN\\"}", kernel_name = "k"} : x\n'
            '%1 = stablehlo.custom_call @Sharding(%0) {backend_config = "s"}')
    assert stablehlo_hashes.blanked(text) == (
        '%0 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config '
        '= "", kernel_name = "k"} : x\n'
        '%1 = stablehlo.custom_call @Sharding(%0) {backend_config = "s"}')
    hlo = ("  %f.1 = (f32[8]{0}, s32[]) fusion(%p), kind=kLoop\n"
           "  ROOT %t = f32[8]{0} get-tuple-element(%f.1), index=0\n")
    assert stablehlo_hashes.opcodes(hlo) == {"fusion": 1,
                                             "get-tuple-element": 1}
