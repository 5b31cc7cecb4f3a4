"""Gather-sum passes a chunk of tokens, over the routed layers and the
window's steps (``zoo_moe_choice_passes_total`` over
``zoo_moe_chunk_runs_total``, from ``model.last_fit_report``): the most held
choices any token of a chunk has, so 1 under a router whose every token
holds one choice here and ``top_k`` (8) the static worst case. It is a
maximum over a chunk's tokens, so one token decides it, and it is what the
cell's rate follows from seed to seed (PERF.md section 7). A program whose
report has no such counter (before PR 29) reads nothing."""


def read(view):
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if not report or not report.get("chunk_runs"):
        return None
    return report["choice_passes"] / report["chunk_runs"]
