"""Host time the DataplaneExecutor spent compiling inside the window
(``phase_us["compile"]`` summed over the window's runs): 0 when set-up warmed
every shape."""


def read(run):
    if not run.answered:
        return None
    return sum(b["phase_us"].get("compile", 0.0) for b in run.batches()) / 1e3
