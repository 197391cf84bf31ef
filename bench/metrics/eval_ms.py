"""Host time of the test-set evaluation per evaluating round: the mean
length of the program's ``eval`` spans (``repro.obs``) in the traced
window, one to each evaluation.  None where the program recorded none."""


def read(ctx):
    evals = [e["dur_s"] for e in ctx["spans"] if e["name"] == "eval"]
    if not evals:
        return None
    return sum(evals) / len(evals) * 1e3
