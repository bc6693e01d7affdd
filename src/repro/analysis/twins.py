"""Twin-parity checker: structural equality of the NumPy / jnp samplers.

The engine's validity argument leans on *bit-identical twins*: every
counter-RNG sampler exists twice — a pure-NumPy reference in
:mod:`repro.core.events` and a jnp implementation in
:mod:`repro.kernels.sim_step` — and the waste optima are only validated
against simulation because both engines draw identical streams.  The
known-answer tests pin the pair dynamically; this pass pins it
*statically*: editing one twin without the other is a failure at
analysis time, with a unified diff of the divergent subtrees.

How it works
============

``TWIN_REGISTRY`` declares the pairs.  Each side is parsed (source only
— the NumPy side must stay importable without JAX, and nothing is
executed) and normalized modulo the known cross-dialect idioms:

- ``np`` / ``jnp`` / ``math`` namespace prefixes are stripped
  (``np.where`` ↔ ``jnp.where``), and ``_gamma`` ↔ ``math.gamma``
  canonicalize to one name;
- docstrings, annotations, defaults and decorators are dropped;
- ``with np.errstate(...):`` blocks are inlined (NumPy-only masking of
  intentional overflow in the integer mixers);
- dtype plumbing is erased: single-argument casts
  (``np.uint32(x)`` / ``dtype(x)``), ``asarray(x[, dtype])``,
  ``.astype(...)``, parameters and call arguments named ``dtype``;
- ``np.power(a, b)`` rewrites to ``a ** b``, ``np.pi`` substitutes its
  IEEE value, and literal arithmetic constant-folds (so
  ``2.0 * np.pi`` ↔ ``2.0 * 3.141592653589793`` agree);
- ``raise`` payloads are dropped (both sides must *fail* on the same
  branch, the message may differ) and post-normalization identity
  assignments (``k0 = k0``, the residue of an unwrapped ``asarray``
  coercion) are deleted.

What survives normalization is the computation's shape — operators,
operand order, control flow, select chains (including the dual-``where``
pow strength-reduction both sides mirror deliberately).  Any residual
difference is reported.

A second check keeps the registry itself honest: every twin function
must carry a ``# repro-twin: <dotted path of its counterpart>`` comment
above its ``def``, and the set of annotations in the twin modules must
match the registry exactly (both directions), so a new twin cannot land
annotated-but-unregistered or registered-but-unannotated.
"""

from __future__ import annotations

import ast
import difflib
import math
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["TwinPair", "TWIN_REGISTRY", "check_twins", "compare_pair"]

_TWIN_RE = re.compile(r"#\s*repro-twin:\s*([\w.]+)")

#: namespaces whose attribute access is a dialect detail, not structure
_NAMESPACES = {"np", "jnp", "numpy", "math", "lax"}

#: single-argument calls that are dtype coercions, not computation
_CASTS = {
    "uint8", "uint16", "uint32", "uint64",
    "int8", "int16", "int32", "int64",
    "float16", "float32", "float64", "bool_", "dtype",
}

_FOLD_BINOPS = {
    ast.Add: lambda a, b: a + b,
    ast.Sub: lambda a, b: a - b,
    ast.Mult: lambda a, b: a * b,
    ast.Div: lambda a, b: a / b,
    ast.Pow: lambda a, b: a ** b,
    ast.Mod: lambda a, b: a % b,
    ast.FloorDiv: lambda a, b: a // b,
    ast.LShift: lambda a, b: a << b,
    ast.RShift: lambda a, b: a >> b,
}


@dataclass(frozen=True)
class TwinPair:
    """One registered NumPy/jnp twin: module dotted paths + function names."""

    np_module: str
    np_func: str
    jnp_module: str
    jnp_func: str

    @property
    def label(self) -> str:
        return (
            f"{self.np_module}.{self.np_func} <-> "
            f"{self.jnp_module}.{self.jnp_func}"
        )


#: the declared twin registry — extend this when adding a sampler pair
#: (and annotate both defs with ``# repro-twin:``, see module docstring)
TWIN_REGISTRY: Tuple[TwinPair, ...] = (
    TwinPair("repro.core.events", "threefry2x32",
             "repro.kernels.sim_step", "threefry2x32"),
    TwinPair("repro.core.events", "splitmix64",
             "repro.kernels.sim_step", "splitmix64"),
    TwinPair("repro.core.events", "uniform24",
             "repro.kernels.sim_step", "uniform24"),
    TwinPair("repro.core.events", "gap_transform_np",
             "repro.kernels.sim_step", "gap_transform"),
    TwinPair("repro.core.events", "gap_transform_indexed_np",
             "repro.kernels.sim_step", "gap_transform_indexed"),
    # the lane machine's clock tolerance (float32 clocks on the TPU)
    TwinPair("repro.core.batch_sim", "clock_tol",
             "repro.kernels.sim_step", "clock_tol"),
    # the differentiable analytic waste layer (branchless table models)
    TwinPair("repro.core.analytic", "precision_from_fp",
             "repro.kernels.analytic", "precision_from_fp"),
    TwinPair("repro.core.analytic", "young_waste",
             "repro.kernels.analytic", "young_waste"),
    TwinPair("repro.core.analytic", "exact_waste",
             "repro.kernels.analytic", "exact_waste"),
    TwinPair("repro.core.analytic", "migration_waste",
             "repro.kernels.analytic", "migration_waste"),
    TwinPair("repro.core.analytic", "instant_waste",
             "repro.kernels.analytic", "instant_waste"),
    TwinPair("repro.core.analytic", "nockpt_waste",
             "repro.kernels.analytic", "nockpt_waste"),
    TwinPair("repro.core.analytic", "withckpt_waste",
             "repro.kernels.analytic", "withckpt_waste"),
    TwinPair("repro.core.analytic", "two_level_waste",
             "repro.kernels.analytic", "two_level_waste"),
    TwinPair("repro.core.analytic", "silent_waste",
             "repro.kernels.analytic", "silent_waste"),
    TwinPair("repro.core.analytic", "cell_waste",
             "repro.kernels.analytic", "cell_waste"),
)


def _module_path(root: Path, dotted: str) -> Path:
    return root / "src" / Path(*dotted.split(".")).with_suffix(".py")


def _module_source(
    root: Path, dotted: str, sources: Optional[Dict[str, str]]
) -> str:
    if sources and dotted in sources:
        return sources[dotted]
    return _module_path(root, dotted).read_text()


def _find_function(source: str, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.parse(source).body:
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


class _Normalize(ast.NodeTransformer):
    """Erase the np/jnp dialect differences listed in the module doc."""

    # -- namespaces and names ------------------------------------------

    def visit_Attribute(self, node: ast.Attribute):
        self.generic_visit(node)
        if isinstance(node.value, ast.Name) and node.value.id in _NAMESPACES:
            if node.attr == "pi":
                return ast.copy_location(ast.Constant(value=math.pi), node)
            return ast.copy_location(ast.Name(id=node.attr, ctx=node.ctx), node)
        return node

    def visit_Name(self, node: ast.Name):
        if node.id == "_gamma":
            return ast.copy_location(ast.Name(id="gamma", ctx=node.ctx), node)
        return node

    # -- calls: casts, asarray/astype, power, dtype plumbing -----------

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        node.args = [
            a for a in node.args
            if not (isinstance(a, ast.Name) and a.id == "dtype")
        ]
        node.keywords = [
            k for k in node.keywords
            if not (isinstance(k.value, ast.Name) and k.value.id == "dtype")
        ]
        fn = node.func
        # .astype(X) -> receiver
        if isinstance(fn, ast.Attribute) and fn.attr == "astype":
            return fn.value
        if isinstance(fn, ast.Name) and not node.keywords:
            if fn.id in ("asarray", "array") and 1 <= len(node.args) <= 2:
                return node.args[0]
            if fn.id in _CASTS and len(node.args) == 1:
                return node.args[0]
            if fn.id == "power" and len(node.args) == 2:
                return ast.copy_location(
                    ast.BinOp(
                        left=node.args[0], op=ast.Pow(), right=node.args[1]
                    ),
                    node,
                )
        return node

    # -- constant folding ----------------------------------------------

    def visit_BinOp(self, node: ast.BinOp):
        self.generic_visit(node)
        fold = _FOLD_BINOPS.get(type(node.op))
        if (
            fold is not None
            and isinstance(node.left, ast.Constant)
            and isinstance(node.right, ast.Constant)
            and isinstance(node.left.value, (int, float))
            and isinstance(node.right.value, (int, float))
        ):
            try:
                return ast.copy_location(
                    ast.Constant(value=fold(node.left.value, node.right.value)),
                    node,
                )
            except (ZeroDivisionError, OverflowError, ValueError):
                return node
        return node

    def visit_UnaryOp(self, node: ast.UnaryOp):
        self.generic_visit(node)
        if isinstance(node.operand, ast.Constant) and isinstance(
            node.operand.value, (int, float)
        ):
            if isinstance(node.op, ast.USub):
                return ast.copy_location(
                    ast.Constant(value=-node.operand.value), node
                )
            if isinstance(node.op, ast.UAdd):
                return node.operand
        return node

    # -- statements -----------------------------------------------------

    def visit_With(self, node: ast.With):
        self.generic_visit(node)
        if all(
            isinstance(item.context_expr, ast.Call)
            and isinstance(item.context_expr.func, ast.Name)
            and item.context_expr.func.id == "errstate"
            for item in node.items
        ):
            return node.body  # inline: NumPy-only overflow masking
        return node

    def visit_Assign(self, node: ast.Assign):
        self.generic_visit(node)
        if (
            len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Name)
            and node.targets[0].id == node.value.id
        ):
            return None  # residue of an unwrapped asarray coercion
        return node

    def visit_Raise(self, node: ast.Raise):
        return ast.copy_location(ast.Raise(exc=None, cause=None), node)

    def visit_arg(self, node: ast.arg):
        node.annotation = None
        return node

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self.generic_visit(node)
        if (
            node.body
            and isinstance(node.body[0], ast.Expr)
            and isinstance(node.body[0].value, ast.Constant)
            and isinstance(node.body[0].value.value, str)
        ):
            node.body = node.body[1:] or [ast.Pass()]
        node.args.args = [a for a in node.args.args if a.arg != "dtype"]
        node.args.defaults = []
        node.args.kw_defaults = [None] * len(node.args.kwonlyargs)
        node.returns = None
        node.decorator_list = []
        return node


def normalize_function(fn: ast.FunctionDef, name: str) -> ast.FunctionDef:
    """Normalized deep copy of one twin's AST, renamed to ``name`` so the
    two sides of a pair compare under a common function name."""
    fn = ast.parse(ast.unparse(fn)).body[0]  # deep copy via round-trip
    assert isinstance(fn, ast.FunctionDef)
    fn.name = name
    out = _Normalize().visit(fn)
    ast.fix_missing_locations(out)
    return out


def compare_pair(
    root: Path,
    pair: TwinPair,
    sources: Optional[Dict[str, str]] = None,
) -> List[str]:
    """Check one registered pair; returns error strings (empty = parity).

    ``sources`` optionally overrides module sources by dotted path
    (used by the mutation tests to perturb one side in memory)."""
    errors: List[str] = []
    np_src = _module_source(root, pair.np_module, sources)
    jnp_src = _module_source(root, pair.jnp_module, sources)
    np_fn = _find_function(np_src, pair.np_func)
    jnp_fn = _find_function(jnp_src, pair.jnp_func)
    if np_fn is None:
        errors.append(
            f"{pair.label}: {pair.np_module}.{pair.np_func} not found"
        )
    if jnp_fn is None:
        errors.append(
            f"{pair.label}: {pair.jnp_module}.{pair.jnp_func} not found"
        )
    if errors:
        return errors
    a = normalize_function(np_fn, "twin")
    b = normalize_function(jnp_fn, "twin")
    if ast.dump(a) == ast.dump(b):
        return []
    diff = "\n".join(
        difflib.unified_diff(
            ast.unparse(a).splitlines(),
            ast.unparse(b).splitlines(),
            fromfile=f"{pair.np_module}.{pair.np_func} (normalized)",
            tofile=f"{pair.jnp_module}.{pair.jnp_func} (normalized)",
            lineterm="",
        )
    )
    return [
        f"{pair.label}: twins diverge structurally — edit both sides "
        f"together (or extend the normalizer for a new shared idiom)\n{diff}"
    ]


def _annotations(source: str) -> Dict[str, str]:
    """``# repro-twin:`` comments mapped ``func name -> counterpart``.

    A twin comment binds to the next ``def`` at most 3 lines below it
    (other directives / decorators may sit between)."""
    out: Dict[str, str] = {}
    lines = source.splitlines()
    for i, text in enumerate(lines):
        m = _TWIN_RE.search(text)
        if not m:
            continue
        for follow in lines[i + 1:i + 4]:
            dm = re.match(r"\s*def\s+(\w+)", follow)
            if dm:
                out[dm.group(1)] = m.group(1)
                break
    return out


def check_annotations(
    root: Path,
    registry: Sequence[TwinPair] = TWIN_REGISTRY,
    sources: Optional[Dict[str, str]] = None,
) -> List[str]:
    """Registry <-> ``# repro-twin:`` comment consistency, both ways."""
    errors: List[str] = []
    modules = {p.np_module for p in registry} | {p.jnp_module for p in registry}
    annotated = {
        mod: _annotations(_module_source(root, mod, sources))
        for mod in modules
    }
    expected: Dict[str, Dict[str, str]] = {mod: {} for mod in modules}
    for p in registry:
        expected[p.np_module][p.np_func] = f"{p.jnp_module}.{p.jnp_func}"
        expected[p.jnp_module][p.jnp_func] = f"{p.np_module}.{p.np_func}"
    for mod in sorted(modules):
        got, want = annotated[mod], expected[mod]
        for func in sorted(set(want) - set(got)):
            errors.append(
                f"{mod}.{func}: registered twin is missing its "
                f"'# repro-twin: {want[func]}' comment"
            )
        for func in sorted(set(got) - set(want)):
            errors.append(
                f"{mod}.{func}: '# repro-twin:' comment on an "
                "unregistered function — add it to TWIN_REGISTRY"
            )
        for func in sorted(set(got) & set(want)):
            if got[func] != want[func]:
                errors.append(
                    f"{mod}.{func}: twin comment names {got[func]!r} "
                    f"but the registry pairs it with {want[func]!r}"
                )
    return errors


def check_twins(
    root: Path,
    registry: Sequence[TwinPair] = TWIN_REGISTRY,
    sources: Optional[Dict[str, str]] = None,
) -> List[str]:
    """Run the full twin-parity pass; returns error strings (empty = OK)."""
    errors = check_annotations(root, registry, sources)
    for pair in registry:
        errors.extend(compare_pair(root, pair, sources))
    return errors
