"""Prefix cache (serving/prefix_cache.py): prompt tokens served from a
cached prefix, as a share of the prompt tokens of the requests admitted
in the window.  Numerator from the cache's own ``tokens_saved`` counter,
read at the window's start and end."""


def read(run):
    a = run.counters["start"].get("prefix_cache")
    b = run.counters["end"].get("prefix_cache")
    prompt = sum(len(r.req.prompt) for r in run.records
                 if r.admit_t is not None and 0.0 <= r.admit_t < run.seconds)
    if a is None or b is None or prompt == 0:
        return None
    return 100.0 * (b["tokens_saved"] - a["tokens_saved"]) / prompt
