"""Repo-specific AST lint passes for the DeServe serving core.

Three pass families, all pure-AST (no imports of the analysed code):

**host-sync** — device→host materialisations reachable from the serve
hot path (``Engine.step``, both backends' ``decode``/``prefill_step``,
and both persistent tick jits).  Flags ``.item()`` / ``.tolist()`` /
``jax.device_get`` / ``block_until_ready`` anywhere in the reachable
set, and ``np.array``/``np.asarray``/``int()``/``float()``/``bool()``
applied to *device-tracked* values — names bound from jit entry points
(any ``*jit*`` attribute call), ``jnp.*``/``jax.*`` producers, or the
sampler helpers.  One accidental sync per tick is a WAN-scale stall.

**offload-sync** — blocking host materialisations inside the KV
offloader's *engaged window* (``DoubleBufferOffloader.ensure_resident``
by default; ``offload_windows`` in config).  The double-buffer schedule
only hides swap cost if the swap-out is an enqueued async copy — a
``np.asarray`` / ``jax.device_get`` / ``block_until_ready`` there
serialises the D2H behind the tick and re-opens the very stall the
offloader exists to hide.  The deliberate sync fallback
(``async_swap=False``) carries a reasoned suppression.

**obs-hot-path** — flight-recorder discipline for ``repro.obs``: a
recording call (any dotted call routed through a recorder name —
``self.recorder.span(...)``, ``rec.link_send(...)``; ``obs_roots`` in
config — or a program span, ``obs.span(...)``) is flagged inside a tick-jit body (the recorder is host-side
only — a call under tracing either fails or bakes one trace's stamps
into the compiled graph), and, anywhere in the reachable hot set, when
an argument references a *device-tracked* value — recording must read
only host scalars the engine already materialised, or it re-opens the
very sync the flight recorder is designed never to add.

**retrace hazards** —
  * ``retrace-jit``: ``jax.jit`` / ``shard_map`` constructed inside a
    hot-path function (recompiles or re-caches per call);
  * ``retrace-branch``: a Python ``if``/``while`` on a traced value
    inside a tick-jit body (shape/ndim/dtype attribute access is static
    and allowed) — branches on traced data either fail to trace or bake
    in one trace's path;
  * ``retrace-nonhashable``: ``jax.jit(functools.partial(f, kw=[...]))``
    with a mutable-literal kwarg — unhashable partial state defeats the
    jit cache and retraces every call.
  Host-materialisation of traced values inside a tick-jit body is
  reported as ``host-sync`` (it is also a concretization error).

**PRNG hygiene** —
  * ``prng-reuse``: one key name consumed by two or more ``jax.random``
    sampling calls without re-binding (identical streams);
  * ``prng-fold-drop``: a sampling call keyed by a raw ``PRNGKey`` or a
    single-level ``fold_in`` chain — the serving discipline is
    ``fold_in(fold_in(PRNGKey(seed), request_id), token_idx)``; a
    shorter chain drops ``request_id`` or ``token_idx`` and collapses
    streams across requests or positions.

Suppressions: ``# repro-audit: allow(<rule>[, <rule>...]) — <reason>``
on the offending line or the line above.  Under
``--strict-suppressions`` every suppression must carry a non-empty
reason and must actually suppress something (``bad-suppression`` /
``unused-suppression``).

Configuration lives in ``[tool.repro-audit]`` of ``pyproject.toml``
(hot-path roots, traced tick functions, device-typed parameter names);
the baked-in defaults below mirror it so the tool runs on a bare tree.
"""

from __future__ import annotations

import argparse
import ast
import io
import re
import sys
import tokenize
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

# mirrors [tool.repro-audit] in pyproject.toml (pyproject wins when found)
DEFAULT_HOT_ROOTS = [
    "serving.engine:OfflineEngine.step",
    "serving.backend:LocalBackend.decode",
    "serving.backend:LocalBackend.prefill_step",
    "serving.backend:PipelinedBackend.decode",
    "serving.backend:PipelinedBackend.prefill_step",
    "core.pipeline:pipeline_decode_tick",
    "core.pipeline:pipeline_prefill_chunk_tick",
]
DEFAULT_TRACED_FNS = [
    "core.pipeline:pipeline_decode_tick",
    "core.pipeline:pipeline_prefill_chunk_tick",
    "core.pipeline:_pipeline_pass",
    "serving.backend:LocalBackend._decode_fn",
    "serving.backend:_SlotCacheBackend._chunk_fn",
    "serving.backend:_SlotCacheBackend._prefill_fn",
]
# function parameters that carry device arrays into hot-path helpers
# (pure AST cannot see types; the serve seam passes logits rows around)
DEFAULT_DEVICE_PARAMS = ["logits", "logits_row"]
# the offloader's engaged window: functions that run between ticks and
# must only *enqueue* copies, never block on them
DEFAULT_OFFLOAD_WINDOWS = [
    "core.offload:DoubleBufferOffloader.ensure_resident",
    "core.offload:DoubleBufferOffloader._stage_out",
]

# names a flight-recorder handle travels under: a call whose dotted path
# routes through one of these (``self.recorder.span``, ``rec.fault``) is
# an obs recording call for the obs-hot-path pass
DEFAULT_OBS_ROOTS = ["recorder", "rec"]
# the program-span helper: a call by one of these dotted names is a
# recording call too
OBS_CALLS = {"obs.span", "repro.obs.span"}

RULES = ("host-sync", "offload-sync", "obs-hot-path", "retrace-jit",
         "retrace-branch", "retrace-nonhashable", "prng-reuse",
         "prng-fold-drop", "bad-suppression", "unused-suppression")

# calls that force a device→host sync wherever they appear in the hot set
ALWAYS_SYNC = {"jax.device_get", "jax.block_until_ready"}
SYNC_METHODS = {"item", "block_until_ready"}
# host materialisers: flagged only when fed a device-tracked value
HOST_CASTS = {"int", "float", "bool"}
HOST_NP = {"np.array", "np.asarray", "np.copy", "numpy.array",
           "numpy.asarray", "numpy.copy"}
# attribute reads that are static under tracing (never a concretization)
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "sharding"}
# call-name prefixes/names whose results live on device
DEVICE_PREFIXES = ("jnp.", "jax.")
DEVICE_NAMES = {"sample_batched", "fold_in_steps", "token_logprobs",
                "_sample_first", "slot_view", "slot_merge"}
# jax/jnp calls whose results are NOT device arrays
DEVICE_EXCEPTIONS = {"jnp.dtype", "jax.device_get", "jax.devices",
                     "jax.local_devices", "jax.device_count",
                     "jax.tree.map", "jax.tree_util.tree_map",
                     "jax.sharding.Mesh", "jax.block_until_ready"}

SAMPLER_KEY_ARG = {"jax.random.categorical": 0, "sample_batched": 1,
                   "jax.random.gumbel": 0}
RANDOM_CONSUMERS = {"categorical", "normal", "uniform", "bernoulli",
                    "gumbel", "randint", "truncated_normal", "permutation",
                    "choice", "bits"}
KEY_MAKERS = {"jax.random.PRNGKey", "jax.random.key"}


@dataclass
class AuditConfig:
    hot_roots: List[str] = field(default_factory=lambda:
                                 list(DEFAULT_HOT_ROOTS))
    traced_fns: List[str] = field(default_factory=lambda:
                                  list(DEFAULT_TRACED_FNS))
    device_params: List[str] = field(default_factory=lambda:
                                     list(DEFAULT_DEVICE_PARAMS))
    offload_windows: List[str] = field(default_factory=lambda:
                                       list(DEFAULT_OFFLOAD_WINDOWS))
    obs_roots: List[str] = field(default_factory=lambda:
                                 list(DEFAULT_OBS_ROOTS))


def _parse_toml_section(text: str, section: str) -> Dict[str, List[str]]:
    """Minimal TOML-subset reader for ``key = ["a", "b", ...]`` entries of
    one section — python3.10 has no tomllib and the audit config needs
    nothing richer."""
    out: Dict[str, List[str]] = {}
    in_section = False
    key: Optional[str] = None
    buf = ""
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].rstrip()
        if re.match(r"\s*\[", line):
            in_section = line.strip() == f"[{section}]"
            key = None
            continue
        if not in_section or not line.strip():
            continue
        if key is None:
            m = re.match(r"\s*([\w-]+)\s*=\s*(.*)", line)
            if not m:
                continue
            key, buf = m.group(1), m.group(2)
        else:
            buf += " " + line.strip()
        if buf.count("[") and buf.count("]") >= buf.count("["):
            out[key] = re.findall(r"\"([^\"]*)\"|'([^']*)'", buf)
            out[key] = [a or b for a, b in out[key]]
            key, buf = None, ""
    return out


def load_config(start: Path) -> AuditConfig:
    """Read ``[tool.repro-audit]`` from the nearest ``pyproject.toml`` at
    or above ``start``; fall back to the baked-in defaults."""
    cfg = AuditConfig()
    p = start if start.is_dir() else start.parent
    for d in [p, *p.resolve().parents]:
        pj = d / "pyproject.toml"
        if pj.is_file():
            try:
                sect = _parse_toml_section(pj.read_text(),
                                           "tool.repro-audit")
            except OSError:
                break
            if sect.get("hot_roots"):
                cfg.hot_roots = sect["hot_roots"]
            if sect.get("traced_fns"):
                cfg.traced_fns = sect["traced_fns"]
            if sect.get("device_params"):
                cfg.device_params = sect["device_params"]
            if sect.get("offload_windows"):
                cfg.offload_windows = sect["offload_windows"]
            if sect.get("obs_roots"):
                cfg.obs_roots = sect["obs_roots"]
            break
    return cfg


# ---------------------------------------------------------------------------
# Indexing: functions, calls, suppressions
# ---------------------------------------------------------------------------


@dataclass
class Violation:
    rule: str
    path: str
    line: int
    msg: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.msg}"


@dataclass
class Suppression:
    path: str
    line: int
    rules: Set[str]
    reason: str
    used: bool = False


@dataclass
class FuncInfo:
    module: str                   # dotted module ("repro.serving.backend")
    qual: str                     # "Class.method" or "func"
    path: str
    node: ast.AST                 # FunctionDef / AsyncFunctionDef

    @property
    def full(self) -> str:
        return f"{self.module}:{self.qual}"

    @property
    def bare(self) -> str:
        return self.qual.rsplit(".", 1)[-1]


_SUPPRESS_RE = re.compile(
    r"#\s*repro-audit:\s*allow\(([^)]*)\)\s*(?:[-—–]+\s*(\S.*))?")


def _collect_suppressions(path: str, source: str) -> List[Suppression]:
    # real COMMENT tokens only — the syntax quoted in a docstring or
    # string literal is documentation, not a suppression
    out = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        comments = [(t.start[0], t.string) for t in tokens
                    if t.type == tokenize.COMMENT]
    except tokenize.TokenError:
        comments = [(i, line) for i, line in
                    enumerate(source.splitlines(), start=1)
                    if line.lstrip().startswith("#")]
    for i, text in comments:
        m = _SUPPRESS_RE.search(text)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            out.append(Suppression(path=path, line=i, rules=rules,
                                   reason=(m.group(2) or "").strip()))
    return out


def _dotted(node: ast.AST) -> Optional[str]:
    """Best-effort dotted name of a Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    if parts:                       # e.g. x[0].foo → ".foo" tail only
        return "." + ".".join(reversed(parts))
    return None


class _FuncCollector(ast.NodeVisitor):
    def __init__(self, module: str, path: str):
        self.module = module
        self.path = path
        self.stack: List[str] = []
        self.funcs: List[FuncInfo] = []

    def visit_ClassDef(self, node: ast.ClassDef):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _func(self, node):
        qual = ".".join(self.stack + [node.name])
        self.funcs.append(FuncInfo(self.module, qual, self.path, node))
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _func
    visit_AsyncFunctionDef = _func


@dataclass
class FileIndex:
    path: str
    module: str
    tree: ast.AST
    funcs: List[FuncInfo]
    suppressions: List[Suppression]


def _module_name(path: Path, root: Path) -> str:
    try:
        rel = path.resolve().relative_to(root.resolve())
    except ValueError:
        rel = Path(path.name)
    parts = list(rel.with_suffix("").parts)
    if parts and parts[0] == "src":
        parts = parts[1:]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) or path.stem


def index_paths(paths: Sequence[Path]) -> Tuple[List[FileIndex],
                                                List[Violation]]:
    files: List[FileIndex] = []
    errors: List[Violation] = []
    for root in paths:
        py_files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        base = root.parent if root.is_file() else root
        for f in py_files:
            try:
                src = f.read_text()
                tree = ast.parse(src, filename=str(f))
            except (OSError, SyntaxError) as e:
                errors.append(Violation("parse-error", str(f),
                                        getattr(e, "lineno", 0) or 0,
                                        str(e)))
                continue
            mod = _module_name(f, base)
            col = _FuncCollector(mod, str(f))
            col.visit(tree)
            files.append(FileIndex(str(f), mod, tree, col.funcs,
                                   _collect_suppressions(str(f), src)))
    return files, errors


# ---------------------------------------------------------------------------
# Call graph + reachability
# ---------------------------------------------------------------------------


def _calls_of(fn: FuncInfo) -> List[Tuple[str, ast.Call]]:
    out = []
    for node in ast.walk(fn.node):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name:
                out.append((name, node))
    return out


def _match_spec(fn: FuncInfo, spec: str) -> bool:
    return fn.full.endswith(spec) or fn.qual == spec


def reachable_functions(files: Sequence[FileIndex],
                        roots: Sequence[str]) -> Set[str]:
    """Transitive closure of the hot roots over a name-based call graph:
    a call ``a.b.c(...)`` edges to every function named ``c`` anywhere in
    the indexed tree.  Deliberately an over-approximation — reachability
    gates *reporting*, and a missed edge hides a real sync while a
    spurious edge only asks for one explained suppression."""
    by_bare: Dict[str, List[FuncInfo]] = {}
    by_full: Dict[str, FuncInfo] = {}
    for fi in files:
        for fn in fi.funcs:
            by_bare.setdefault(fn.bare, []).append(fn)
            by_full[fn.full] = fn
    work = [fn.full for fi in files for fn in fi.funcs
            if any(_match_spec(fn, r) for r in roots)]
    seen: Set[str] = set(work)
    while work:
        fn = by_full[work.pop()]
        for name, _ in _calls_of(fn):
            bare = name.rsplit(".", 1)[-1]
            for callee in by_bare.get(bare, ()):
                if callee.full not in seen:
                    seen.add(callee.full)
                    work.append(callee.full)
    return seen


# ---------------------------------------------------------------------------
# Dataflow-lite helpers
# ---------------------------------------------------------------------------


def _is_device_call(name: str) -> bool:
    if name in DEVICE_EXCEPTIONS:
        return False
    bare = name.rsplit(".", 1)[-1]
    if "jit" in bare:
        return True
    if bare in DEVICE_NAMES:
        return True
    return name.startswith(DEVICE_PREFIXES) and name not in DEVICE_EXCEPTIONS


def _refs_tracked(node: ast.AST, tracked: Set[str]) -> bool:
    """Does ``node`` read a tracked name other than through a static
    attribute (``.shape`` / ``.ndim`` / ``.dtype`` ...)?"""
    if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return isinstance(node.ctx, ast.Load) and node.id in tracked
    return any(_refs_tracked(c, tracked) for c in ast.iter_child_nodes(node))


def _expr_is_device(node: ast.AST, tracked: Set[str]) -> bool:
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name and _is_device_call(name):
            return True
    return _refs_tracked(node, tracked)


def _target_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for el in target.elts:
            out.extend(_target_names(el))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    return []                       # attribute / subscript targets: skip


def _tracked_names(fn: ast.AST, seed: Set[str]) -> Set[str]:
    """Fixpoint taint: names bound (directly or transitively) to device
    values inside ``fn``.  Loop targets and nested-function parameters are
    NOT tainted: iterating a pytree walks static container structure, and
    closures are usually invoked with static arguments."""
    tracked = set(seed)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if node.value is None:
                    continue
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.NamedExpr):
                value, targets = node.value, [node.target]
            else:
                continue
            if _expr_is_device(value, tracked):
                for t in targets:
                    for name in _target_names(t):
                        if name not in tracked:
                            tracked.add(name)
                            changed = True
    return tracked


def _pos_params(node) -> List[str]:
    a = node.args
    return [p.arg for p in [*a.posonlyargs, *a.args]
            if p.arg not in ("self", "cls")]


# ---------------------------------------------------------------------------
# Pass 1: host-sync detector
# ---------------------------------------------------------------------------


def _host_sync_pass(files: Sequence[FileIndex], cfg: AuditConfig,
                    reachable: Set[str]) -> List[Violation]:
    out: List[Violation] = []
    for fi in files:
        for fn in fi.funcs:
            if fn.full not in reachable:
                continue
            seed = {p for p in _pos_params(fn.node)
                    if p in cfg.device_params}
            tracked = _tracked_names(fn.node, seed)
            for name, call in _calls_of(fn):
                tag = None
                if name in ALWAYS_SYNC:
                    tag = (f"`{name}` blocks on device work — one call "
                           "per tick is a WAN-scale stall in the serve "
                           "loop")
                elif name.rsplit(".", 1)[-1] in SYNC_METHODS and \
                        "." in name:
                    tag = (f"`.{name.rsplit('.', 1)[-1]}()` synchronously "
                           "materialises a device value on host")
                elif name.endswith(".tolist") and _refs_tracked(
                        call.func, tracked):
                    tag = "`.tolist()` on a device value syncs the stream"
                elif name in HOST_NP and any(
                        _refs_tracked(a, tracked) for a in call.args):
                    tag = (f"`{name}` on a device value forces a "
                           "device→host copy inside the tick loop")
                elif name in HOST_CASTS and call.args and _refs_tracked(
                        call.args[0], tracked):
                    tag = (f"`{name}()` on a traced/device value blocks "
                           "until the device catches up (and fails under "
                           "jit tracing)")
                if tag:
                    out.append(Violation(
                        "host-sync", fi.path, call.lineno,
                        f"{fn.qual}: {tag}"))
    return out


# ---------------------------------------------------------------------------
# Pass 1b: offload-sync detector
# ---------------------------------------------------------------------------


def _offload_sync_pass(files: Sequence[FileIndex],
                       cfg: AuditConfig) -> List[Violation]:
    """Any blocking host materialisation inside the offloader's engaged
    window.  Unlike ``host-sync`` this does not gate on a device-tracked
    dataflow: the window functions exist solely to move pool slices, so
    *every* ``np.asarray``/``device_get``/``block_until_ready`` there is
    a copy that should have been an enqueued async one."""
    out: List[Violation] = []
    stall = ("serialises the D2H swap behind the tick — store the "
             "enqueued jax copy (async_swap) so the transfer hides "
             "under the next tick's compute")
    for fi in files:
        for fn in fi.funcs:
            if not any(_match_spec(fn, w) for w in cfg.offload_windows):
                continue
            for name, call in _calls_of(fn):
                bare = name.rsplit(".", 1)[-1]
                tag = None
                if name in ALWAYS_SYNC:
                    tag = f"`{name}` in the offload window {stall}"
                elif bare in SYNC_METHODS and "." in name:
                    tag = f"`.{bare}()` in the offload window {stall}"
                elif name.endswith(".tolist"):
                    tag = f"`.tolist()` in the offload window {stall}"
                elif name in HOST_NP:
                    tag = f"`{name}` in the offload window {stall}"
                if tag:
                    out.append(Violation("offload-sync", fi.path,
                                         call.lineno, f"{fn.qual}: {tag}"))
    return out


# ---------------------------------------------------------------------------
# Pass 1c: obs-hot-path detector
# ---------------------------------------------------------------------------


def _obs_pass(files: Sequence[FileIndex], cfg: AuditConfig,
              reachable: Set[str]) -> List[Violation]:
    """Flight-recorder discipline (``repro.obs``).  A recording call is
    any dotted call whose path routes through an ``obs_roots`` name
    (``self.recorder.span``, ``rec.link_send``), or a program span
    (``obs.span``).  Two failure modes:

    * inside a tick-jit body (``traced_fns``) *every* recording call is
      flagged — the recorder is host-side; under tracing the call either
      fails or bakes one trace's stamps into the compiled graph;
    * in a reachable hot-path function, a recording call whose arguments
      reference a device-tracked value is flagged — materialising it for
      the trace adds the very device→host sync the recorder's contract
      ("record only values the engine already holds") forbids.
    """
    out: List[Violation] = []
    roots = set(cfg.obs_roots)

    def _is_obs(name: str) -> bool:
        if name in OBS_CALLS:
            return True
        parts = name.split(".")
        # the final component is the method; any earlier component being
        # a recorder name makes this a recording call
        return len(parts) >= 2 and any(p in roots for p in parts[:-1])

    for fi in files:
        for fn in fi.funcs:
            in_jit = any(_match_spec(fn, t) for t in cfg.traced_fns)
            if not in_jit and fn.full not in reachable:
                continue
            tracked: Optional[Set[str]] = None
            for name, call in _calls_of(fn):
                if not _is_obs(name):
                    continue
                if in_jit:
                    out.append(Violation(
                        "obs-hot-path", fi.path, call.lineno,
                        f"{fn.qual}: recording call `{name}` inside a "
                        "tick-jit body — the flight recorder is "
                        "host-side only; record after the jit returns"))
                    continue
                if tracked is None:     # computed once per function
                    seed = {p for p in _pos_params(fn.node)
                            if p in cfg.device_params}
                    tracked = _tracked_names(fn.node, seed)
                vals = [*call.args, *(k.value for k in call.keywords)]
                if any(_refs_tracked(a, tracked) for a in vals):
                    out.append(Violation(
                        "obs-hot-path", fi.path, call.lineno,
                        f"{fn.qual}: recording call `{name}` "
                        "materialises a traced/device value — record "
                        "only host scalars the engine already holds"))
    return out


# ---------------------------------------------------------------------------
# Pass 2: retrace hazards
# ---------------------------------------------------------------------------


def _retrace_pass(files: Sequence[FileIndex], cfg: AuditConfig,
                  reachable: Set[str]) -> List[Violation]:
    out: List[Violation] = []
    for fi in files:
        for fn in fi.funcs:
            # (a) jit/shard_map built inside the hot path
            if fn.full in reachable:
                for name, call in _calls_of(fn):
                    bare = name.rsplit(".", 1)[-1]
                    if bare in ("jit", "shard_map") and (
                            name.startswith(("jax.", "jjit."))
                            or bare == "shard_map" or name == "jit"):
                        out.append(Violation(
                            "retrace-jit", fi.path, call.lineno,
                            f"{fn.qual}: `{name}` constructed inside the "
                            "serve hot path — a fresh jit wrapper "
                            "compiles (or re-hashes) per call; hoist to "
                            "__init__ or module scope"))
            # (b) non-hashable static args anywhere
            for name, call in _calls_of(fn):
                if name.rsplit(".", 1)[-1] != "jit":
                    continue
                for arg in call.args:
                    if not (isinstance(arg, ast.Call) and
                            (_dotted(arg.func) or "").endswith("partial")):
                        continue
                    for kw in arg.keywords:
                        if isinstance(kw.value, (ast.List, ast.Dict,
                                                 ast.Set, ast.ListComp,
                                                 ast.DictComp,
                                                 ast.SetComp)):
                            out.append(Violation(
                                "retrace-nonhashable", fi.path,
                                kw.value.lineno,
                                f"{fn.qual}: `functools.partial` kwarg "
                                f"`{kw.arg}` is a mutable literal — "
                                "unhashable partial state defeats the "
                                "jit cache and retraces every call"))
            # (c) Python branches on traced values inside tick jits
            if not any(_match_spec(fn, t) for t in cfg.traced_fns):
                continue
            node = fn.node
            kwonly = {p.arg for p in node.args.kwonlyargs}
            traced = _tracked_names(node, set(_pos_params(node)) - kwonly)
            nested_params: Set[str] = set()
            for sub in ast.walk(node):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) and sub is not \
                        node:
                    nested_params |= set(_pos_params(sub))
            traced -= nested_params     # closures get static call args
            for sub in ast.walk(node):
                if isinstance(sub, (ast.If, ast.While)) and _refs_tracked(
                        sub.test, traced):
                    out.append(Violation(
                        "retrace-branch", fi.path, sub.lineno,
                        f"{fn.qual}: Python branch on a traced value — "
                        "use lax.cond/jnp.where, or mark the argument "
                        "static (shape/ndim/dtype reads are fine)"))
                elif isinstance(sub, ast.Call):
                    name = _dotted(sub.func)
                    if name in HOST_NP and any(
                            _refs_tracked(a, traced) for a in sub.args):
                        out.append(Violation(
                            "host-sync", fi.path, sub.lineno,
                            f"{fn.qual}: `{name}` on a traced value "
                            "inside a tick jit — concretization error "
                            "under tracing"))
                    elif name in HOST_CASTS and sub.args and _refs_tracked(
                            sub.args[0], traced):
                        out.append(Violation(
                            "host-sync", fi.path, sub.lineno,
                            f"{fn.qual}: `{name}()` on a traced value "
                            "inside a tick jit — concretization error "
                            "under tracing"))
    return out


# ---------------------------------------------------------------------------
# Pass 3: PRNG hygiene
# ---------------------------------------------------------------------------


def _key_depth(node: ast.AST, env: Dict[str, Optional[int]]
               ) -> Optional[int]:
    """Fold-chain depth of a key expression: ``PRNGKey(s)`` is 0,
    ``fold_in(k, x)`` is depth(k)+1, a name looks up its binding; anything
    else (params, splits, helper results) is unknown → None (never
    flagged)."""
    if isinstance(node, ast.Call):
        name = _dotted(node.func) or ""
        if name in KEY_MAKERS:
            return 0
        if name.endswith("fold_in") and node.args:
            inner = _key_depth(node.args[0], env)
            return None if inner is None else inner + 1
        return None
    if isinstance(node, ast.Name):
        return env.get(node.id)
    return None


def _prng_pass(files: Sequence[FileIndex]) -> List[Violation]:
    out: List[Violation] = []
    for fi in files:
        for fn in fi.funcs:
            # bindings of key-producing expressions (single-assignment only:
            # re-bound names drop out of both rules)
            bound: Dict[str, List[Tuple[Optional[int], int]]] = {}
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Assign) and len(node.targets) == 1:
                    names = _target_names(node.targets[0])
                    if len(names) != 1:
                        continue
                    d = _key_depth(node.value, {
                        k: v[0][0] for k, v in bound.items()
                        if len(v) == 1})
                    if d is not None or (isinstance(node.value, ast.Call)
                                         and (_dotted(node.value.func) or ""
                                              ).endswith(("fold_in",
                                                          "split"))):
                        bound.setdefault(names[0], []).append(
                            (d, node.lineno))
            env: Dict[str, Optional[int]] = {
                k: v[0][0] for k, v in bound.items() if len(v) == 1}
            key_names = {k for k, v in bound.items() if len(v) == 1}

            uses: Dict[str, List[int]] = {}
            for name, call in _calls_of(fn):
                bare = name.rsplit(".", 1)[-1]
                # fold-drop: sampling keyed below the (seed, request_id,
                # token_idx) discipline
                if name in SAMPLER_KEY_ARG:
                    idx = SAMPLER_KEY_ARG[name]
                    if idx < len(call.args):
                        d = _key_depth(call.args[idx], env)
                        if d is not None and d < 2:
                            what = ("raw PRNGKey — request_id AND "
                                    "token_idx dropped" if d == 0 else
                                    "single fold_in — token_idx (or "
                                    "request_id) dropped")
                            out.append(Violation(
                                "prng-fold-drop", fi.path, call.lineno,
                                f"{fn.qual}: sampling keyed by a "
                                f"{what}; derive keys as fold_in("
                                "fold_in(PRNGKey(seed), request_id), "
                                "token_idx)"))
                # reuse: the same key name feeding >= 2 sampling calls
                if bare in RANDOM_CONSUMERS and (
                        name.startswith("jax.random.")
                        or name.startswith("random.")):
                    for arg in call.args:
                        if isinstance(arg, ast.Name) and \
                                arg.id in key_names:
                            uses.setdefault(arg.id, []).append(call.lineno)
            for key, lines in uses.items():
                if len(lines) >= 2:
                    out.append(Violation(
                        "prng-reuse", fi.path, sorted(lines)[1],
                        f"{fn.qual}: key `{key}` consumed by "
                        f"{len(lines)} jax.random calls (lines "
                        f"{sorted(lines)}) without re-binding — "
                        "identical streams; split or fold_in per use"))
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _apply_suppressions(violations: List[Violation],
                        files: Sequence[FileIndex],
                        strict: bool) -> List[Violation]:
    sup_by_file: Dict[str, List[Suppression]] = {
        fi.path: fi.suppressions for fi in files}
    kept: List[Violation] = []
    for v in violations:
        hit = None
        for s in sup_by_file.get(v.path, ()):
            if v.rule in s.rules and s.line in (v.line, v.line - 1):
                hit = s
                break
        if hit is None:
            kept.append(v)
        else:
            hit.used = True
    if strict:
        for fi in files:
            for s in fi.suppressions:
                bad = s.rules - set(RULES)
                if bad:
                    kept.append(Violation(
                        "bad-suppression", s.path, s.line,
                        f"unknown rule(s) {sorted(bad)} — valid: "
                        f"{', '.join(RULES)}"))
                if not s.reason:
                    kept.append(Violation(
                        "bad-suppression", s.path, s.line,
                        "suppression without a written reason — every "
                        "exemption must explain itself: "
                        "# repro-audit: allow(<rule>) — <why>"))
                elif not s.used and not bad:
                    kept.append(Violation(
                        "unused-suppression", s.path, s.line,
                        f"allow({', '.join(sorted(s.rules))}) suppresses "
                        "nothing on this or the next line — stale after "
                        "a fix; delete it"))
    return kept


def run_lint(paths: Sequence[Path], config: Optional[AuditConfig] = None,
             *, strict_suppressions: bool = False,
             rules: Optional[Sequence[str]] = None) -> List[Violation]:
    """Run every pass over ``paths`` (files or directories), apply
    suppressions, return surviving violations sorted by location."""
    cfg = config or load_config(Path(paths[0]) if paths else Path("."))
    files, violations = index_paths([Path(p) for p in paths])
    reachable = reachable_functions(files, cfg.hot_roots)
    violations += _host_sync_pass(files, cfg, reachable)
    violations += _offload_sync_pass(files, cfg)
    violations += _obs_pass(files, cfg, reachable)
    violations += _retrace_pass(files, cfg, reachable)
    violations += _prng_pass(files)
    if rules:
        want = set(rules) | {"parse-error"}
        violations = [v for v in violations if v.rule in want]
    violations = _apply_suppressions(violations, files,
                                     strict_suppressions)
    return sorted(violations, key=lambda v: (v.path, v.line, v.rule))


def _default_root() -> Path:
    # the src/ tree the installed repro package lives in
    return Path(__file__).resolve().parents[2]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repro-audit static analysis for the serving core")
    ap.add_argument("paths", nargs="*", help="files or directories "
                    "(default: the src tree of the installed package)")
    ap.add_argument("--strict-suppressions", action="store_true",
                    help="require a written reason on every suppression "
                         "and flag suppressions that match nothing")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset of rules to report "
                         f"(all: {', '.join(RULES)})")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for r in RULES:
            print(r)
        return 0
    paths = [Path(p) for p in args.paths] or [_default_root()]
    for p in paths:
        if not p.exists():
            print(f"repro-audit: no such path: {p}", file=sys.stderr)
            return 2
    rules = [r.strip() for r in args.rules.split(",")] if args.rules \
        else None
    violations = run_lint(paths, strict_suppressions=args.strict_suppressions,
                          rules=rules)
    for v in violations:
        print(v.format())
    n = len(violations)
    print(f"repro-audit: {n} violation(s)" if n else
          "repro-audit: clean")
    return 1 if violations else 0
