"""Model step: the prefills' share of the chip's bf16 peak (%): the
operations the prefills of the traced window need (``flops.prefill_flops``)
over their executables' summed device time times the peak."""
from harness import flops, model
from harness.stats import module_seconds


def read(run):
    secs = module_seconds(run, r"prefill")
    tokens = run.records.get("prefill_tokens")
    if not secs or not tokens or run.peaks is None:
        return None
    s = model.Shapes.of(run.cell.config)
    need = sum(flops.prefill_flops(s, n) for n in tokens)
    return 100.0 * need / (sum(secs) * run.peaks["bf16_flops_per_s"])
