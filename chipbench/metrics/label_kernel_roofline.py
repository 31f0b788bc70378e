"""Label kernel: share of its roofline in the traced sub-window.

The kernel does integer compares and scatter-mins, so its least time is
its bytes over the chip's HBM bandwidth.  Bytes are what the calls need,
from their shapes: the genomes actually scored (unpadded), the graph's
tables once per call and the labels written.  Calls and genomes come from
the engine's counters read at the sub-window's two ends; the reading is
left out when that call count differs from the kernel's in the trace."""
from chipbench.kernels import genome_bytes, graph_edge_counts, table_bytes
from chipbench.peaks import peaks

KERNEL = "jit__labels_jax"


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("counters_start") or not tr.get("counters_stop"):
        return None
    k = tr["kernels"].get(KERNEL)
    a, b = tr["counters_start"], tr["counters_stop"]
    calls = b["batches"] - a["batches"]
    genomes = b["states_scored"] - a["states_scored"]
    if not k or not k["calls"] or k["calls"] != calls or not k["seconds"]:
        return None
    g = rec["config"]["graph"]
    n, m, chain, extra = graph_edge_counts(g["nodes"], g["fields"])
    need = genomes * genome_bytes(n, m) + calls * table_bytes(n, chain, extra)
    least_s = need / peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / k["seconds"]
