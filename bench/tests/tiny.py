"""Tiny cells for tests on the CPU: the real cells' files at small sizes,
with the real cells' limits.  The LM computes in float32 here: at these
widths bfloat16 rounding alone moves the loss by more than the limit set
for the published widths, and the tests are about what the check catches,
not about the precision of the chip's runs."""
from bench.run import BENCH, load_json

SIZES = {
    "qwen2-0.5b.ici4": (
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, vocab_size=256, num_hidden_layers=2,
             compute_dtype="float32"),
        dict(seq=16)),
}


def spec(cell: str) -> dict:
    """A loadable cell spec at a size a test run can hold."""
    from bench import run
    s = run.load_cell(cell)
    cfg_kw, tr_kw = SIZES[cell]
    s["config"] = dict(s["config"], **cfg_kw)
    s["traffic"] = dict(s["traffic"], **tr_kw)
    s["per_layer"], s["readers"], s["peaks"] = [], {}, {}
    return s


def one_device(s: dict) -> dict:
    """The cell on one device: its clients mixed by the program's row mix
    in place of the exchange between chips, so that a test of the check
    runs in one process (the four-device path has its own test)."""
    return dict(s, traffic=dict(s["traffic"], mix="sparse"))


def limits(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")
