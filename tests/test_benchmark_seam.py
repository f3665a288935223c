"""The seam between the benchmark and the program: every per-layer metric
(``perfbench/layer_metrics/*.json``) that takes a NAME from the program
names one the program still has.

A reader finds its series on ``/metrics`` by family (and label), its
kernel in the device trace by the ``pallas_call``'s name, its step program
by the jitted function's name, its host span by the ``kgct.*`` annotation.
Rename one of those in the package and the metric reads nothing: on the
chip that is ``output_malformed``, here it is this test. The check is
static, over the package's source, so it runs in a second and needs no
server; what the names MEAN is the readers' own tests' business
(``perfbench/tests``).
"""

import ast
import json
import re
from pathlib import Path

import pytest

from kubernetes_gpu_cluster_tpu.analysis.core import LintModule
from kubernetes_gpu_cluster_tpu.observability.phases import (
    PHASES, SPAN_PREFIX)

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "kubernetes_gpu_cluster_tpu"
METRICS = sorted((REPO / "perfbench" / "layer_metrics").glob("*.json"))

# Readers by what their metric file takes from the program.
PROM_READERS = {"prom_hist_mean", "prom_hist_mean_where",
                "prom_hist_quantile", "prom_counter_delta",
                "prom_counter_delta_diff", "prom_gauge_sampled",
                "prom_gauge_window_mean", "prom_ratio",
                "prom_counter_ratio"}
FAMILY_KEYS = ("family", "minus", "num", "den")
KERNEL_READERS = {"kernel_flops_share", "hc_kernel_hbm_share",
                  "kda_kernel_hbm_share", "latent_kernel_hbm_share",
                  "ssm_kernel_hbm_share", "block_attend_kernel_hbm_share"}
# Nothing from the program: the client's own clock, byte models over
# another metric (``step_metric``), the device's busy intervals.
NO_PROGRAM_NAME = {"client", "roofline", "hc_step_hbm_share",
                   "hybrid_step_hbm_share", "kda_step_hbm_share",
                   "latent_moe_step_hbm_share", "trace_idle",
                   "dsa_step_hbm_share", "block_step_hbm_share",
                   # ... and arrays of the configuration's own shapes
                   "dsa_rows_attend_hbm_share", "dsa_select_share"}
# Needles of ``trace_op_share`` that are XLA's words, not the program's.
XLA_OWN = {"custom-call", "custom_call", "pallas"}

_SERIES = re.compile(r"(?:# (?:TYPE|HELP) )?(kgct_[a-z0-9_]+)(\{[^}]*\})?")
_HIST_SUFFIX = re.compile(r"_(sum|count|bucket)$")


def _call_name(node: ast.Call) -> str:
    f = node.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")


def _str(node) -> str | None:
    return node.value if (isinstance(node, ast.Constant)
                          and isinstance(node.value, str)) else None


class Program:
    """What the package's source says it renders, names and annotates."""

    def __init__(self):
        self.labels: dict[str, set] = {}    # family -> its label names
        self.histograms: set = set()
        self.strings: set = set()           # every string constant
        self.kernels: set = set()           # pallas_call(name=...)
        self.spans = {SPAN_PREFIX + p for p in PHASES}
        for path in PACKAGE.rglob("*.py"):
            tree = ast.parse(path.read_text())
            docstrings = {
                id(n.body[0].value) for n in ast.walk(tree)
                if isinstance(n, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                  ast.AsyncFunctionDef))
                and n.body and isinstance(n.body[0], ast.Expr)}
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    self._call(node)
                text = _str(node)
                if text is None or id(node) in docstrings:
                    continue
                self.strings.add(text)
                m = _SERIES.match(text)     # a series is named at the START
                if m:
                    self.labels.setdefault(m.group(1), set()).update(
                        re.findall(r'(\w+)="', m.group(2) or ""))
        engine = LintModule(PACKAGE / "engine" / "engine.py", root=REPO)
        self.step_functions = {getattr(j.node, "name", "")
                               for j in engine.jitted_functions} - {""}

    def _call(self, node: ast.Call) -> None:
        name, first = _call_name(node), None
        if node.args:
            first = _str(node.args[0])
        kw = {k.arg: k.value for k in node.keywords}
        if name == "Histogram" and first:
            self.histograms.add(first)
            labels = kw.get("labels")
            self.labels.setdefault(first, set()).update(
                _str(e) for e in getattr(labels, "elts", []))
        elif name == "pallas_call" and _str(kw.get("name")):
            self.kernels.add(_str(kw["name"]))
        elif name == "span" and first:
            self.spans.add(SPAN_PREFIX + first)

    def family(self, name: str) -> str | None:
        """The rendered family ``name`` reads, or None."""
        if name in self.labels:
            return name
        base = _HIST_SUFFIX.sub("", name)
        return base if base in self.histograms else None

    def names_a_kernel(self, needle: str) -> bool:
        if needle.startswith("%") and needle.endswith("."):
            return needle[1:-1] in self.kernels     # the whole name
        return any(needle in k for k in self.kernels)


@pytest.fixture(scope="module")
def program():
    return Program()


def test_the_scan_resolves_the_programs_real_names(program):
    """Guard against a vacuous pass: the scan must find what the package
    has today, by the forms the package writes it in."""
    assert {"paged_decode", "flash_prefill", "flash_prefill_hist",
            "ssm_chunk", "kda_update", "hc_pre"} <= program.kernels
    assert {"mixed_step", "decode_window_greedy"} <= program.step_functions
    assert "kgct_step_device_seconds" in program.histograms
    assert program.labels["kgct_step_device_seconds"] == {"kind"}
    assert program.labels["kgct_step_tokens_total"] == {"kind", "real"}
    assert {"kgct.step", "kgct.worker.wait",
            "kgct.device_dispatch"} <= program.spans
    assert "kgct_not_a_series" not in program.labels


@pytest.mark.parametrize("path", METRICS, ids=lambda p: p.stem)
def test_metric_names_what_the_program_has(path, program):
    spec = json.loads(path.read_text())
    reader = spec["reader"]
    if reader in NO_PROGRAM_NAME:
        pytest.skip(f"reader {reader} takes no name from the program")
    if reader in PROM_READERS:
        families = [spec[k] for k in FAMILY_KEYS if k in spec]
        assert families, f"{path.name}: no family to read"
        for name in families:
            assert program.family(name), (
                f"{path.name}: no series {name} is rendered by the package")
        labels = dict(spec.get("labels", {}))
        if "label" in spec:
            labels[spec["label"]] = spec.get("numerator", [])
        have = program.labels[program.family(families[0])]
        for label, values in labels.items():
            assert label in have, (
                f"{path.name}: {families[0]} has labels {sorted(have)}, "
                f"not {label}")
            for v in ([values] if isinstance(values, str) else values):
                assert v.isdigit() or v in program.strings, (
                    f"{path.name}: no {label}={v!r} in the package")
    elif reader == "trace_op_share":
        for needle in spec["contains"]:
            assert needle in XLA_OWN or program.names_a_kernel(needle), (
                f"{path.name}: no pallas_call is named {needle!r}")
    elif reader in KERNEL_READERS:
        assert program.names_a_kernel(spec["contains"]), (
            f"{path.name}: no pallas_call is named {spec['contains']!r}")
    elif reader == "dsa_index_roofline":
        # operations told by the configuration's own shapes, inside one of
        # the program's step functions
        assert any(spec["module"] in f for f in program.step_functions), (
            f"{path.name}: no jitted step function is named "
            f"{spec['module']!r}")
    elif reader == "trace_module_time":
        assert any(spec["contains"] in f for f in program.step_functions), (
            f"{path.name}: no jitted step function is named "
            f"{spec['contains']!r}")
    elif reader == "trace_idle_by_span":
        wanted = list(spec.get("spans", [])) + (
            [spec["self_of"]] if "self_of" in spec else [])
        for pattern in wanted:
            assert any(s.startswith(pattern[:-1]) if pattern.endswith("*")
                       else s == pattern for s in program.spans), (
                f"{path.name}: the package writes no span {pattern}")
    elif reader == "trace_step_lead":
        # The names are the reader's own constants.
        from perfbench.readers import trace_step_lead as lead
        assert lead.DISPATCH in program.spans
        for module in lead.MODULE_OF_KIND.values():
            assert any(module in f for f in program.step_functions), module
    else:
        pytest.fail(f"{path.name}: reader {reader} is not classified here: "
                    "say what it takes from the program")


def test_a_block_model_s_names_are_the_ones_its_metrics_read(program):
    """ISSUE 50's tracing: the counters ``block_tokens_per_pass`` divides
    (two families without labels, which is what ``prom_counter_ratio``
    takes), the two beside them, the histogram, the kernel
    ``block_attend_kernel_hbm_share`` finds by name, the three named
    scopes, and the step functions ``decode_step_ms`` / ``mixed_step_ms``
    find a block model's programs by."""
    for family in ("kgct_block_passes_total",
                   "kgct_block_commit_passes_total",
                   "kgct_block_tokens_transferred_total",
                   "kgct_block_positions_computed_total"):
        assert program.labels.get(family) == set(), family
    assert "kgct_block_passes_per_block" in program.histograms
    assert "block_attend" in program.kernels
    for scope in ("kgct.block.attend", "kgct.block.transfer",
                  "kgct.block.commit"):
        assert scope in program.strings, scope
    block = LintModule(PACKAGE / "engine" / "block.py", root=REPO)
    names = {getattr(j.node, "name", "") for j in block.jitted_functions}
    assert any("decode_window" in n for n in names), names
    assert any("mixed_step" in n for n in names), names
    for name in ("block_step_hbm_share", "block_attend_kernel_hbm_share",
                 "block_tokens_per_pass"):
        spec = json.loads((REPO / "perfbench" / "layer_metrics"
                           / f"{name}.json").read_text())
        assert (REPO / "perfbench" / "readers"
                / f"{spec['reader']}.py").is_file()
