"""Model step (latent-attention MoE): the prefills' share of the chip's
bf16 peak (%): the operations the prefills of the traced window need
(``mla_flops.prefill_flops``: 6 routed and the shared experts a token,
causal pairs at the decompressed widths, the head at the last position)
over their executables' summed device time times the peak."""
from harness import mla_flops, mla_model
from harness.stats import module_seconds


def read(run):
    secs = module_seconds(run, r"prefill")
    tokens = run.records.get("prefill_tokens")
    if not secs or not tokens or run.peaks is None:
        return None
    s = mla_model.Shapes.of(run.cell.config)
    need = sum(mla_flops.prefill_flops(s, n) for n in tokens)
    return 100.0 * need / (sum(secs) * run.peaks["bf16_flops_per_s"])
