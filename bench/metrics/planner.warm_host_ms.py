"""Mean host time per submit of the window before the executor runs: heavy-hitter
statistics, plan-cache lookup or compile, and verification
(``stats_us + compile_us + verify_us`` of each SessionResult)."""


def read(run):
    res = run.session_results()
    if not res:
        return None
    return sum(r.stats_us + r.compile_us + r.verify_us for r in res) / len(res) / 1e3
