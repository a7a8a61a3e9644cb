"""Mean milliseconds per frame in the named stages of the frame ledger
(``obs/timeline.py stage_breakdown``), over the traced seconds. Host clocks."""


def read(run, stages):
    ledger = (run.get("trace") or {}).get("stages")
    if not ledger or not ledger.get("frames"):
        return None
    return sum(ledger["stages_ms"][s] for s in stages)
