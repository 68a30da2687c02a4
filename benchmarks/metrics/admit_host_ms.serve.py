"""The host's cost of one admission, in ms: the median over the window's
inline admissions of the request's ``serve.plan`` spans (its page plan
and commit, failed attempts included) plus its ``serve.admit`` (host
preparation, the prefill's dispatch, the admit program's dispatch), less
any ``serve.sync`` under them.  Nothing where no
``serve.dispatch{program=prefill}`` is found: an older ``serve.admit``
has no plan beside it, and the two would not be the same quantity.  On
standard error: the mean split plan / preparation (``serve.admit``'s
self time) / prefill dispatch / admit dispatch, the admissions an
iteration that admitted any, and every ``serve.dispatch`` of the window
by ``program`` (``sched_host_ms_per_step.serve`` sums them under one
name)."""


def read(run):
    import spans
    from common import percentile
    sp = spans.load(run)
    if not sp or not sp.named("serve.dispatch", program="prefill"):
        return None
    admits = [s for s in sp.named("serve.admit")
              if "chunks" not in s.fields]
    if not admits:
        return None
    plans = {}
    for s in sp.all:
        if s.name == "serve.plan":
            plans.setdefault(s.fields.get("req"), []).append(s)
    split = {"plan": 0.0, "preparation": 0.0, "prefill dispatch": 0.0,
             "admit dispatch": 0.0}
    costs, by_step = [], {}
    for a in admits:
        under = sp.descendants(a)
        plan = sum(spans.ms(s) for s in plans.get(a.fields.get("req"), ()))
        sync = sum(spans.ms(s) for s in under if s.name == "serve.sync")
        costs.append(plan + spans.ms(a) - sync)
        split["plan"] += plan
        split["preparation"] += sp.self_ms(a)
        for s in under:
            if s.name == "serve.dispatch" and \
                    s.fields.get("program") in ("prefill", "admit"):
                split[s.fields["program"] + " dispatch"] += spans.ms(s)
        by_step[a.parent] = by_step.get(a.parent, 0) + 1
    n = len(admits)
    spans.note("admit_host_ms.serve",
               f"{n} admissions, mean {sum(costs) / n:.3f} ms: "
               + ", ".join(f"{k} {v / n:.3f}" for k, v in split.items())
               + f"; {n / len(by_step):.2f} admissions an iteration that "
               f"admitted any ({len(by_step)} iterations, at most "
               f"{max(by_step.values())})")
    by_program = {}
    for s in sp.named("serve.dispatch"):
        rec = by_program.setdefault(s.fields.get("program", "?"), [0, 0.0])
        rec[0] += 1
        rec[1] += spans.ms(s)
    spans.note("admit_host_ms.serve", "serve.dispatch by program: "
               + ", ".join(f"{k} {n} calls {t:.1f} ms ({t / n:.3f} each)"
                           for k, (n, t) in sorted(
                               by_program.items(), key=lambda kv: -kv[1][1])))
    return percentile(costs, 50)
