"""What every architecture's "the cell resolves its names" test asks of the
benchmark's table, in one place: by what the table SAYS (a metric's
`workloads`, a roofline's `cost_fn`), never by a name's prefix or a count of
names, so that a `benchmark` PR can merge per-cell copies of one formula
into a shared file and a PR can list a cell in a shared metric."""

from benchmarks.lib import spec


def resolved(cell: str):
    """`spec.load_cell(cell)`, checked: the cell reports exactly the
    per-layer metrics whose `workloads` list it (nothing of another cell's
    leaks into its resolution), each once, inside the driver's 128; an
    end-to-end metric beside `setup_s`; and every roofline's cost function
    is in the cell's own table.  -> (loaded, the cost functions its
    rooflines name)."""
    loaded = spec.load_cell(cell)
    bench = spec.load_benchmark()
    names = [m["name"] for m in loaded["layer_metrics"]]
    assert names and len(set(names)) == len(names)
    assert names == [m["name"] for m in bench["per_layer"]
                     if cell in m.get("workloads", [cell])]
    assert len(bench["per_layer"]) <= 128
    assert {m["name"] for m in loaded["end_to_end"]} > {"setup_s"}
    kernels = {k["cost_fn"] for m in loaded["layer_metrics"]
               for k in m.get("kernels", ())}
    assert kernels <= set(loaded["cost_fns"])
    return loaded, kernels
