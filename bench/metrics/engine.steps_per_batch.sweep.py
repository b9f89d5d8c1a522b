"""Device programs launched per Session.execute call in the traced window.
Each padded sweep window is one program; any other program the session
launches for a batch counts too, so this reads every device program a
batch costs."""


def read(run):
    t = run.trace
    if t is None or not t.execute_spans:
        return None
    return t.executions / t.execute_spans
