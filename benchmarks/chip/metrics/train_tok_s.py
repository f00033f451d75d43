"""Non-pad target tokens of every step completed in the window, over the
window: from the first timed step's dispatch to the state after the last
step being ready."""


def read(run: dict):
    w = run["window"]
    return w["tgt_tokens"] / w["seconds"]
