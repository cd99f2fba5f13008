"""KV store: prompt tokens that hit the prefix cache, as a share of all
prompt tokens (%), from ``Request.hit_tokens``. A count: a hit prompt is
still prefilled whole."""


def read(run):
    reqs = run.records.get("requests") or []
    total = sum(len(r["prompt"]) for r in reqs)
    return 100.0 * sum(r["hit_tokens"] for r in reqs) / total if total else None
