"""Median host time of one engine loop turn that ran a decode step,
outside the wait for the device: the loop's annotations
``llm.step.select`` + ``llm.step.dispatch`` + ``llm.step.fanout`` of the
same turn (profiler trace, host plane). The medians of all four phases,
``llm.step.sync`` among them, go to an earlier line: this metric plus the
sync's median is what ``serve_decode_step_ms_p50`` times from outside."""

from benchmark import program_trace, stats


def read(run):
    pt = program_trace.of_run(run)
    turns = program_trace.step_turns(pt) if pt else []
    if not turns:
        return None
    host = ("llm.step.select", "llm.step.dispatch", "llm.step.fanout")
    run.say("engine_step_phases_ms_p50", turns=len(turns), **{
        k: stats.median(t[k] for t in turns)
        for k in host + ("llm.step.sync",)},
        clock_anchors=program_trace.clock_anchors(pt))
    return stats.median(sum(t[k] for k in host) for t in turns)
