"""device_sets_per_call.att: signature sets per device batch verification
over the window, from the program's counters bls_device_sets_total and
bls_device_batches_total: how full the fixed 32-set chunk is."""


def read(run):
    before, after = run.counters
    calls = after["bls_device_batches_total"] - before["bls_device_batches_total"]
    if not calls:
        return None
    return (after["bls_device_sets_total"] - before["bls_device_sets_total"]) / calls
