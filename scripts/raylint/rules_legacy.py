"""The five pre-raylint check scripts, folded in as registry rules.

Each check keeps a root-parameterized core function so the old
``scripts/check_*.py`` entry points can stay behaviour-compatible thin
shims (tier-1 fixture tests call them against temp trees), while the
registered Rule runs the same logic over the shared parsed-file cache.

Rules: typed-errors, metrics-names, atomic-writes, lazy-jax,
kernel-fallbacks — see each class's `doc` for the contract.
"""

from __future__ import annotations

import ast
import re
from collections import defaultdict
from pathlib import Path
from typing import Iterable, List, Tuple

from .engine import Finding, Project, Rule, register

# --------------------------------------------------------------- typed-errors

_BARE_EXCEPT = re.compile(r"^\s*except\s*:")
_EXC_CLASS = re.compile(r"^class\s+(\w+)\s*\(", re.MULTILINE)


def bare_except_lines(lines) -> List[Tuple[int, str]]:
    return [
        (lineno, "bare 'except:' in the serve path — catch a named "
                 "exception class")
        for lineno, line in enumerate(lines, 1)
        if _BARE_EXCEPT.match(line)
    ]


def check_bare_except(serve_root) -> List[str]:
    """Compat API (shim + fixture tests): old-style strings."""
    errors = []
    for path in sorted(Path(serve_root).rglob("*.py")):
        for lineno, msg in bare_except_lines(path.read_text().splitlines()):
            errors.append(f"{path}:{lineno}: {msg}")
    return errors


def missing_exception_exports(exc_src: str, init_src: str) -> List[str]:
    return [
        name for name in _EXC_CLASS.findall(exc_src)
        if not re.search(rf"\b{re.escape(name)}\b", init_src)
    ]


def check_exports(package_root) -> List[str]:
    """Compat API: every core exception class is exported top-level."""
    package_root = Path(package_root)
    exc_src = (package_root / "core" / "exceptions.py").read_text()
    init_src = (package_root / "__init__.py").read_text()
    return [
        f"core/exceptions.py defines {name} but ray_tpu/__init__.py "
        f"does not export it"
        for name in missing_exception_exports(exc_src, init_src)
    ]


@register
class TypedErrorsRule(Rule):
    name = "typed-errors"
    doc = ("No bare 'except:' under ray_tpu/serve/ (it swallows the typed "
           "resilience errors the router dispatches on); every exception "
           "class in core/exceptions.py is exported from ray_tpu.")

    def check(self, project: Project) -> Iterable[Finding]:
        for sf in project.files_under("ray_tpu/serve/"):
            for lineno, msg in bare_except_lines(sf.lines):
                yield Finding(self.name, sf.rel, lineno, msg)
        exc = project.file("ray_tpu/core/exceptions.py")
        init = project.file("ray_tpu/__init__.py")
        if exc is not None and init is not None:
            for name in missing_exception_exports(exc.text, init.text):
                yield Finding(
                    self.name, exc.rel, 1,
                    f"exception class {name} is not exported from "
                    f"ray_tpu/__init__.py",
                )


# -------------------------------------------------------------- metrics-names

# literal-first-arg metric instantiations; group 1 = constructor,
# group 2 = metric name
_METRIC_PATTERN = re.compile(
    r"""(?<![\w.])(Counter|Gauge|Histogram|
        get_or_create_counter|get_or_create_gauge|get_or_create_histogram)
        \(\s*["']([^"']+)["']""",
    re.VERBOSE,
)
_DIRECT = {"Counter", "Gauge", "Histogram"}
_HISTOGRAMS = {"Histogram", "get_or_create_histogram"}
# the one module allowed to touch sampler internals (it IS the guard)
_GUARD_MODULE = "metrics.py"


def _call_text(text: str, start: int, limit: int = 4000) -> str:
    """The full call expression from the opening paren at/after `start`
    to its balanced close (string-naive: metric registrations never
    embed unbalanced parens in literals)."""
    i = text.index("(", start)
    depth = 0
    for j in range(i, min(len(text), i + limit)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return text[i:j + 1]
    return text[i:i + limit]


def metric_findings(files) -> List[Tuple[str, int, str]]:
    """(relpath, lineno, message) over [(relpath, text)] file pairs."""
    errors: List[Tuple[str, int, str]] = []
    direct_sites = defaultdict(list)  # metric name -> [(rel, lineno)]
    for rel, text in files:
        lines = text.splitlines()
        for match in _METRIC_PATTERN.finditer(text):
            lineno = text.count("\n", 0, match.start()) + 1
            line = lines[lineno - 1].strip()
            if line.startswith(("class ", "def ", "#")):
                continue
            ctor, name = match.group(1), match.group(2)
            if not name.startswith("raytpu_"):
                errors.append((
                    rel, lineno,
                    f"metric {name!r} missing the raytpu_ prefix",
                ))
            if ctor in _DIRECT:
                direct_sites[name].append((rel, lineno))
            if ctor in _HISTOGRAMS:
                call = _call_text(text, match.start())
                if "boundaries" not in call:
                    errors.append((
                        rel, lineno,
                        f"histogram {name!r} registered without explicit "
                        f"boundaries= — the default buckets misfit most "
                        f"latency distributions",
                    ))
        # sampler-guard bypasses (outside the guard module)
        if rel.endswith(f"util/{_GUARD_MODULE}"):
            continue
        for lineno, line in enumerate(lines, 1):
            stripped = line.strip()
            if stripped.startswith("#"):
                continue
            if re.search(r"\._fn\(\s*\)", line):
                # samplers are zero-arg callables; `obj._fn(args)` is
                # some other attribute, not a gauge callback
                errors.append((
                    rel, lineno,
                    "direct sampler call `._fn()` bypasses the "
                    "Gauge.collect sampler-failure guard — sample through "
                    "collect()/prometheus_text()",
                ))
            if re.match(r"\s*def collect\(", line):
                errors.append((
                    rel, lineno,
                    "collect() override outside util/metrics.py — callback "
                    "gauges must go through the guarded Gauge.collect, not "
                    "reimplement it",
                ))
    for name, sites in sorted(direct_sites.items()):
        if len(sites) > 1:
            locs = ", ".join(f"{rel}:{lineno}" for rel, lineno in sites)
            errors.append((
                sites[0][0], sites[0][1],
                f"metric {name!r} directly constructed at {len(sites)} "
                f"sites ({locs}): all but the first silently shadow the "
                f"registered series — use get_or_create_*",
            ))
    return errors


def check(package_root) -> List[str]:
    """Compat API (shim + fixture tests): old-style strings."""
    package_root = Path(package_root)
    files = [
        (str(p.relative_to(package_root.parent)), p.read_text())
        for p in sorted(package_root.rglob("*.py"))
    ]
    return [
        f"{rel}:{lineno}: {msg}" for rel, lineno, msg in metric_findings(files)
    ]


@register
class MetricsNamesRule(Rule):
    name = "metrics-names"
    doc = ("Metric naming + registration discipline: raytpu_ prefix, no "
           "duplicate direct registrations, explicit histogram "
           "boundaries=, no sampler-guard bypasses.")

    def check(self, project: Project) -> Iterable[Finding]:
        files = [
            (sf.rel, sf.text) for sf in project.files_under("ray_tpu/")
        ]
        for rel, lineno, msg in metric_findings(files):
            yield Finding(self.name, rel, lineno, msg)


# -------------------------------------------------------------- atomic-writes

_OPEN_WRITE = re.compile(
    r"""open\(\s*([^,)]+),\s*(?:mode\s*=\s*)?["']wb?["']"""
)
_ATOMIC_WAIVER = re.compile(r"#\s*atomic-ok:")
_REPLACE_WINDOW = 8  # lines after the open() in which os.replace must appear


def atomic_write_lines(lines) -> List[Tuple[int, str]]:
    errors = []
    for lineno, line in enumerate(lines, 1):
        m = _OPEN_WRITE.search(line)
        if m is None:
            continue
        if _ATOMIC_WAIVER.search(line):
            continue
        path_expr = m.group(1)
        if "tmp" in path_expr.lower():
            continue  # staged write: the os.replace commit is the contract
        tail = "\n".join(lines[lineno - 1: lineno - 1 + _REPLACE_WINDOW])
        if "os.replace(" in tail:
            continue
        errors.append((
            lineno,
            f"non-atomic state write (open({path_expr.strip()}, 'w'/'wb') "
            f"without tmp + os.replace); stage to a .tmp sibling and "
            f"os.replace, or waive with '# atomic-ok: <why>'",
        ))
    return errors


def check_file(path) -> List[str]:
    """Compat API (shim + fixture tests): old-style strings."""
    path = Path(path)
    return [
        f"{path}:{lineno}: {msg}"
        for lineno, msg in atomic_write_lines(path.read_text().splitlines())
    ]


def _atomic_targets(root: Path) -> List[Path]:
    targets = sorted((root / "train").rglob("*.py"))
    gcs = root / "core" / "gcs.py"
    if gcs.exists():
        targets.append(gcs)
    return targets


@register
class AtomicWritesRule(Rule):
    name = "atomic-writes"
    doc = ("State-persisting writes in train/ and core/gcs.py must stage "
           "through tmp + os.replace (or carry an '# atomic-ok:' waiver) "
           "so a crash never leaves torn checkpoints/snapshots.")

    def check(self, project: Project) -> Iterable[Finding]:
        targets = (
            project.files_under("ray_tpu/train/")
            + [f for f in (project.file("ray_tpu/core/gcs.py"),) if f]
        )
        for sf in targets:
            for lineno, msg in atomic_write_lines(sf.lines):
                yield Finding(self.name, sf.rel, lineno, msg)


# ------------------------------------------------------------------- lazy-jax

LAZY_JAX_MODULES = (
    "ray_tpu/util/profiling.py",
    "ray_tpu/core/stats.py",
    "ray_tpu/util/tracing.py",
)


def _is_jax_import(node: ast.AST) -> bool:
    if isinstance(node, ast.Import):
        return any(
            alias.name == "jax" or alias.name.startswith("jax.")
            for alias in node.names
        )
    if isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        return mod == "jax" or mod.startswith("jax.")
    return False


def _walk_jax_imports(node, in_function, in_type_checking, out):
    for child in ast.iter_child_nodes(node):
        child_in_fn = in_function or isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        )
        child_tc = in_type_checking or (
            isinstance(node, ast.If)
            and isinstance(node.test, (ast.Name, ast.Attribute))
            and "TYPE_CHECKING" in ast.dump(node.test)
        )
        if _is_jax_import(child) and not child_in_fn and not child_tc:
            out.append(child.lineno)
        _walk_jax_imports(child, child_in_fn, child_tc, out)


def module_level_jax_imports(tree: ast.AST) -> List[int]:
    offenders: List[int] = []
    _walk_jax_imports(tree, False, False, offenders)
    return offenders


_LAZY_JAX_MSG = (
    "module-level jax import — move it inside the function that needs it "
    "(this module must import on jax-less hosts)"
)


@register
class LazyJaxRule(Rule):
    name = "lazy-jax"
    doc = ("profiling/stats/tracing are imported by jax-less observer "
           "hosts: their jax imports must stay function-local.")

    def check(self, project: Project) -> Iterable[Finding]:
        for rel in LAZY_JAX_MODULES:
            sf = project.file(rel)
            if sf is None:
                yield Finding(self.name, rel, 1, "checked module is missing")
                continue
            for lineno in module_level_jax_imports(sf.tree):
                yield Finding(self.name, sf.rel, lineno, _LAZY_JAX_MSG)


# ----------------------------------------------------------- kernel-fallbacks

REQUIRED_FLAGS = (
    # serve throughput round (ragged kernel + SLO autoscaler)
    "serve_ragged_kernel",
    "autoscale_burn_windows",
    "autoscale_pressure_floor",
)

# RayTpuConfig API that is not a flag read
_CFG_METHODS = {"set", "reset", "describe", "as_dict"}


def _uses_pltpu(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == "pltpu":
            return True
    return False


def _pltpu_import_guards(tree: ast.AST) -> List[int]:
    """Lines of try blocks that wrap the `pltpu` import in an except
    ImportError/Exception: the `_HAS_PLTPU = False` idiom that lets a
    module carry on, on the reference, without its kernels."""
    lines = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        handled = any(
            isinstance(h.type, ast.Name)
            and h.type.id in ("ImportError", "Exception")
            or isinstance(h.type, ast.Tuple)
            for h in node.handlers
        )
        if not handled:
            continue
        for child in ast.walk(node):
            if isinstance(child, ast.ImportFrom):
                mod = child.module or ""
                if mod.startswith("jax.experimental.pallas") and any(
                    a.asname == "pltpu" or a.name == "tpu"
                    for a in child.names
                ):
                    lines.append(node.lineno)
    return lines


def _has_reference_oracle(tree: ast.AST) -> bool:
    """A `*reference*` function (pure-XLA ground truth) or an
    `interpret=` kwarg on some call (interpret-mode driver): what the
    tests compare the kernel with."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if "reference" in node.name:
                return True
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "interpret":
                    return True
        if isinstance(node, ast.arg) and node.arg == "interpret":
            return True
    return False


def _reference_in_except(tree: ast.AST) -> List[int]:
    """Lines of except handlers that call a `*reference*` function: the
    `try: kernel / except: reference` swap that hides a kernel the chip's
    compiler refused behind a run that still exits 0."""
    lines = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        for child in ast.walk(node):
            if isinstance(child, ast.Call):
                fn = child.func
                name = getattr(fn, "id", None) or getattr(fn, "attr", "")
                if "reference" in name:
                    lines.append(node.lineno)
                    break
    return lines


def defined_flags(config_tree: ast.AST) -> set:
    flags = set()
    for node in ast.walk(config_tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "define_flag"
            and node.args
            and isinstance(node.args[0], ast.Constant)
        ):
            flags.add(node.args[0].value)
    return flags


def cfg_reads(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, attr) for attribute reads on `cfg` — only in modules that
    import cfg from the config registry and never rebind the name."""
    imports_cfg = any(
        isinstance(node, ast.ImportFrom)
        and (node.module or "").endswith("config")
        and any(a.name == "cfg" for a in node.names)
        for node in ast.walk(tree)
    )
    if not imports_cfg:
        return []
    for node in ast.walk(tree):  # local rebinding shadows the registry
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if isinstance(t, ast.Name) and t.id == "cfg":
                    return []
    return [
        (node.lineno, node.attr)
        for node in ast.walk(tree)
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "cfg"
    ]


@register
class KernelFallbacksRule(Rule):
    name = "kernel-fallbacks"
    doc = ("Pallas TPU kernels keep a reference ORACLE for tests (a "
           "*reference* function or an interpret= driver) and never fall "
           "back to it at run time: no guarded pltpu import, no "
           "except-handler that calls the reference; every cfg.<flag> "
           "read resolves to a define_flag registration in "
           "core/config.py.")

    def check(self, project: Project) -> Iterable[Finding]:
        config = project.file("ray_tpu/core/config.py")
        flags = defined_flags(config.tree) if config is not None else set()
        if config is not None:
            for name in REQUIRED_FLAGS:
                if name not in flags:
                    yield Finding(
                        self.name, config.rel, 1,
                        f"required flag {name!r} is not registered via "
                        f"define_flag",
                    )
        for sf in project.files:
            tree = sf.tree
            if _uses_pltpu(tree):
                for lineno in _pltpu_import_guards(tree):
                    yield Finding(
                        self.name, sf.rel, lineno,
                        "pltpu import is guarded by try/except — one JAX "
                        "is installed; a missing Pallas TPU backend must "
                        "fail at import, not leave the module running on "
                        "its reference",
                    )
                if not _has_reference_oracle(tree):
                    yield Finding(
                        self.name, sf.rel, 1,
                        "Pallas TPU kernels but no reference oracle for "
                        "tests to compare with (need a *reference* "
                        "function or an interpret= driver)",
                    )
                for lineno in _reference_in_except(tree):
                    yield Finding(
                        self.name, sf.rel, lineno,
                        "except-handler calls a *reference* function — a "
                        "kernel that fails must fail the run; selection "
                        "between kernel and reference is a static rule "
                        "on backend and shape",
                    )
            if flags:
                for lineno, attr in cfg_reads(tree):
                    if attr not in flags and attr not in _CFG_METHODS:
                        yield Finding(
                            self.name, sf.rel, lineno,
                            f"cfg.{attr} reads a flag that is not "
                            f"registered in core/config.py defaults",
                        )
