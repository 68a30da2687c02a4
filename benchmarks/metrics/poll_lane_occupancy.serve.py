"""Share of decode lanes that produced a token, from the program's own
polls: over the ``serve.poll`` spans of the window, the tokens the lanes
advanced (``emitted``) less the first tokens that came from a prefill
(``admitted``: one for each lane polled for the first time), over the
decode steps those polls cover (``steps``) x the engine's batch, in
percent.  ``decode_batch_occupancy.serve`` counts the same from the
harness's side."""


def read(run):
    import spans
    sp = spans.load(run)
    polls = sp and sp.named("serve.poll")
    steps = sum(s.fields.get("steps", 0) for s in polls or ())
    if not steps:
        return None
    decoded = sum(s.fields.get("emitted", 0) - s.fields.get("admitted", 0)
                  for s in polls)
    return 100.0 * decoded / (
        steps * run.cfg["serve"]["generation"]["max_batch"])
