"""99th percentile of request latency, ms, read as ``p99_ms`` reads it, in
the cells where host stalls of a tenth of a second swing it too widely to
hold it to a bound end to end."""
import spec


def read(run):
    return spec.reader("p99_ms", run.root)(run)
