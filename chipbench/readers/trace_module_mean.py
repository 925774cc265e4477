"""Mean device time (ms) of one execution of the programs whose name matches
`module_regex`, over the traced slice and the traced chips: the "XLA Modules"
line of the device trace, which trace_reduce.py totals per program name
("jit_prefill_b256(<id>)"). Nothing without a device trace, or where no such
program ran in the slice."""

import re


def read(spec, ctx):
    pattern = re.compile(spec["module_regex"])
    count, seconds = 0, 0.0
    for trace in ctx.traces:
        for dev in trace.get("devices", []):
            for name, row in dev.get("modules", {}).items():
                if pattern.search(name):
                    count += row["count"]
                    seconds += row["seconds"]
    if not count:
        return None
    ctx.notes[f"modules matching {spec['module_regex']}"] = {
        "executions": count, "device_seconds": seconds}
    return 1e3 * seconds / count
