"""Planning and verification of the process's first submit
(``compile_us + verify_us`` of the cold query's SessionResult)."""


def read(run):
    res = run.cold.sessions
    if not res:
        return None
    return sum(r.compile_us + r.verify_us for r in res) / 1e3
