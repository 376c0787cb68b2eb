"""How far one fit's placed dataset sits from the device budget that
decides whether it stays resident: 100 x ``resident_need_bytes`` /
``resident_budget_bytes`` (``MRMRResult.io``), per device, in %.  Above
100 the fit streams every pass.  None where either counter is missing (a
fit that never weighs residency, a backend that reports no memory, or a
program without these counters) or the budget is zero."""


def read(run):
    io = run.io or {}
    need = io.get("resident_need_bytes")
    budget = io.get("resident_budget_bytes")
    if need is None or not budget:
        return None
    return 100.0 * float(need) / float(budget)
