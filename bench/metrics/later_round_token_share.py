"""Scheduler (serving/engine.py admission): the share of the output
tokens shown in the window that belong to a session's later round, whose
prompt the engine extends from the previous round's cached pages.  A
scheduler that starves later rounds behind new sessions reads low."""


def read(run):
    total = later = 0
    for r in run.records:
        n = sum(1 for t in r.tok_t if 0.0 <= t < run.seconds)
        total += n
        later += n if r.spec.round > 1 else 0
    if total == 0 or not any(r.spec.round > 1 for r in run.records):
        return None
    return 100.0 * later / total
