"""``executor.resident_share``: the share of the window's streamed
family-executor calls whose output stayed on the device: of the
``mfit.exec.run`` spans with ``chunks`` > 1, the % with ``resident`` = 1
(the executor's fit rule: the padded output within its share of device
memory, joined on the device, no chunk landed on the host). A program
whose ``exec.run`` spans carry no ``chunks`` gives nothing to read."""
from bench import program_spans as PS


def read(ctx):
    ps = PS.load(ctx)
    if ps is None:
        return None
    streamed = [s for s in ps.in_window("mfit.exec.run")
                if int(s.args.get("chunks", 0)) > 1]
    if not streamed:
        return None
    resident = sum(int(s.args.get("resident", 0)) == 1 for s in streamed)
    return {"value": 100.0 * resident / len(streamed),
            "streamed_calls": len(streamed), "resident_calls": resident}
