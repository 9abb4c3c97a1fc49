"""The comparison that decides `correct`: every answer the window
produced against the plain reference, once the window has closed. Every
comparison is exact (fixed-point decimals): each limit is 0."""
import numpy as np


def compare(dataset, tables, queries, substitute=None):
    """-> {"answers_compared", "answers_wrong", "details"}.
    `substitute(name)` puts another answer in the program's place (the
    control)."""
    want = {}
    wrong, details = 0, []
    for q in queries:
        if q.name not in want:
            want[q.name] = dataset.reference(tables, q.name)
        got = q.rows if substitute is None else substitute(q.name)
        if got is None or dataset.answer_wrong(got, want[q.name]):
            wrong += 1
            if substitute is None and len(details) < 5:
                details.append(
                    f"{q.name} #{q.index}: answer {got}; reference "
                    f"{[r for _, r in want[q.name]]}")
    return {"answers_compared": len(queries), "answers_wrong": wrong,
            "details": details}


def control_lower_precision(dataset, tables):
    """The reference in float32 accumulation, in the program's place."""
    low = {}

    def answer(name):
        if name not in low:
            low[name] = [r for _, r in
                         dataset.reference(tables, name, np.float32)]
        return low[name]
    return answer
