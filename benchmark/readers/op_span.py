"""The benchmark's own spans round each operation, in ms: "register" is the
call into Server.job_register (sent to acknowledged), "late" is how long
after its due time the generator sent it."""

from benchmark.readers.stats import stat as _stat

SPANS = {"register": lambda op: op.acked - op.sent,
         "late": lambda op: op.sent - op.due}


def read(run, span, stat):
    return _stat([SPANS[span](op) * 1e3 for op in run["ops"]], stat)
