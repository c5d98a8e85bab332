"""Share of the routed blocks' row buffers that held an assignment in the
traced steps: 100 x the held assignments (`train_moe_assignments`) over the
rows of the capacities the blocks chose on the device (`train_moe_buffer_rows`,
`ops/moe.py`'s ladder; both summed over a step's routed layers and logged with
the step's metrics, the mean over the steps logged inside the traced ones).
Beside `moe_route_ms` it says which rungs ran: 100 would be buffers as long as
the rows held, a worst-case buffer reads the held share of T x k.  Nothing
where the program logs no `train_moe_buffer_rows` (no ladder, as at the parent
of the PR that added it, or no routed model)."""

from benchmark import mla_scopes


def read(run: dict):
    rows = mla_scopes.traced_counter(run, "train_moe_buffer_rows")
    held = mla_scopes.traced_counter(run, "train_moe_assignments")
    if not rows or held is None:
        return None
    return 100.0 * held / rows
