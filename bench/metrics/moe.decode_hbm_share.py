"""Model step (latent-attention MoE): the decode steps' share of the
chip's HBM bandwidth (%): the bytes the decode steps of the traced window
need (``mla_flops.decode_bytes``: every weight but the embedding table and
the routed experts no token chose, plus the latent cache of the step's
context) over the decode executables' summed device time times the peak.
A request decodes each of its tokens after the first, the k-th with a
context of its prompt and k tokens."""
from harness import mla_flops, mla_model
from harness.stats import module_seconds


def read(run):
    secs = module_seconds(run, r"decode_step")
    reqs = run.records.get("requests")
    if not secs or not reqs or run.peaks is None:
        return None
    s = mla_model.Shapes.of(run.cell.config)
    need = sum(mla_flops.decode_bytes(s, len(r["prompt"]) + k)
               for r in reqs for k in range(1, len(r["generated"])))
    return 100.0 * need / (sum(secs) * run.peaks["hbm_bytes_per_s"])
