"""Share of the HBM-bandwidth roofline the decode rows' attention over their
CHOSEN latent rows reaches: each layer call needs one read of the rows
chosen (``roofline_dsa.chosen_rows_bytes``: min(context, index_topk) rows of
1280 B a running row, for the rows and contexts the client held in flight
during the capture: the bytes of the rows chosen, whatever the
implementation reads), over the device time of the operations that make or
read the chosen rows: those whose HLO text holds an array of ``[rows,
index_topk, row width]`` or its flat form ``[rows x index_topk, row width]``
for a row bucket of the configuration (the gather of the rows out of the
pool, the own row laid in, both products of the attention). A layer call is
one RESULT of the first shape in the model's dtype (the rows with the own
row laid in). Nothing to read (None) on a configuration without an indexer
or a trace without such operations."""


from .. import roofline_dsa as rf
from .. import trace as tr
from .hybrid_step_hbm_share import in_flight


def read(spec, ctx):
    t, peaks, cfg = ctx.get("trace"), ctx.get("peaks"), ctx["config"]
    if t is None or not t.devices or not peaks or not rf.has_indexer(cfg):
        return None
    held = in_flight(ctx)
    if held is None:
        return None
    k, w = cfg["index_topk"], rf.latent_row_elements(cfg)
    whole = [f"[{r},{k},{w}]" for r in cfg["warmup"]["decode_buckets"]]
    shapes = whole + [f"[{r * k},{w}]" for r in cfg["warmup"]["decode_buckets"]]
    calls = seconds = 0.0
    for dev in t.devices:
        mine = [ev for ev in dev.ops if any(s in ev[2] for s in shapes)]
        seconds += sum(e - s for s, e in tr.busy_intervals(mine)) / 1e9
        for _, _, name in mine:
            made = name.partition(" = ")[2].lstrip().split("{")[0]
            if any(made == "bf16" + s or made == "f32" + s for s in whole):
                calls += 1
    if not calls or seconds <= 0:
        return None
    return calls * rf.chosen_rows_bytes(cfg, *held) \
        / peaks["hbm_bytes_per_s"] / seconds * spec.get("scale", 1.0)
