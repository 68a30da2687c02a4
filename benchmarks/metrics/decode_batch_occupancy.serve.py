"""Share of decode lanes that produced a token: tokens decoded in the window
over (decode steps of the window x the engine's batch), in percent."""


def read(run):
    rec = run.records
    steps = rec.get("decode_steps_in_window", 0)
    if not steps:
        return None
    return 100.0 * rec["decode_tokens_in_window"] / (
        steps * run.cfg["serve"]["generation"]["max_batch"])
