"""Family 8 — abstract shape/dtype/sharding rules over jitted programs.

These rules run the shapes.py abstract interpreter over every function
that touches a jitted program, a sharding application, or a quantized
pool pair, seeded with Opaque symbols for parameters and statically-
resolved constants for everything the project model can see (module
constants, cross-module imports, bucket tables). Every rule fires only
on a PROVEN contradiction between two statically-known facts; any TOP
anywhere in the chain keeps the rule silent — see shapes.py for the
no-false-positives-by-construction contract.

RTL801 jit-call-shape-mismatch — the caller's abstract argument shapes,
    pushed through the traced body, hit a provable geometry
    contradiction (reshape element count, matmul contraction,
    broadcast, concatenate). Reported at the CALL SITE, because that is
    where the wrong buffer was fed.
RTL802 donation-alias-mismatch — a `donate_argnums`/`donate_argnames`
    buffer whose abstract shape or dtype provably matches NO output of
    the traced body: XLA cannot alias it, donation silently degrades to
    a copy and the donated buffer is simply dead weight.
RTL803 sharding-nondivisible — a PartitionSpec shards a dim over mesh
    axes whose (statically-resolved) total size does not divide it.
    Meshes resolve exactly like RTL601: literal `Mesh(...)`, module
    constants, cross-module imports; sizes additionally flow from
    `create_device_mesh((...))`-style device shapes.
RTL804 paired-pool-geometry — an int8 K/V pool whose per-token scale
    pool is not the pool's rank and leading axes or is not a float
    dtype, plus the flow form: a function that owns both `X_cache` and
    `X_scale` and writes the pool without ever writing the scales (the
    CoW `copy_block` hazard — stale scales mean wrong magnitudes on
    read-back).
RTL805 bucket-coverage-drift — a width fed to a bucketed jitted program
    that no entry of the statically-resolved bucket table covers: a
    guaranteed cold compile under live traffic, the exact class the
    flight recorder can only report after the fact. Tables come from
    `ElementOf` dims — the join of a loop over a constant tuple or a
    `bucket_for`-style table lookup.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ray_tpu.tools.lint.core import (
    Finding,
    ModuleInfo,
    Rule,
    qualname_of,
)
from ray_tpu.tools.lint.shapes import (
    TOP,
    AbstractArray,
    AbstractMesh,
    Dim,
    ElementOf,
    FLOAT_DTYPES,
    Interp,
    ShardMapProgram,
    ShardingVal,
    SpecVal,
    dims_equal,
    flatten_leaves,
    shape_fully_known,
)

_SHARDING_TRIGGERS = (
    "NamedSharding", "device_put", "with_sharding_constraint",
    "shard_map",
)


# ---------------------------------------------------------------------------
# per-module analysis (shared by all five rules, memoized)
# ---------------------------------------------------------------------------


class _Analysis:
    def __init__(self):
        # (node, message) pairs, deduped on append.
        self.rtl801: List[Tuple[ast.AST, str]] = []
        self.rtl802: List[Tuple[ast.AST, str]] = []
        self.rtl803: List[Tuple[ast.AST, str]] = []
        self.rtl804: List[Tuple[ast.AST, str]] = []
        # jit call sites for the cross-module RTL805 pass:
        # (module, call, program_key, [arg shape tuple | None, ...])
        self.sites: List[tuple] = []
        self._seen: set = set()

    def add(self, bucket: List, node: ast.AST, message: str) -> None:
        key = (id(bucket), id(node), message)
        if key in self._seen:
            return
        self._seen.add(key)
        bucket.append((node, message))


def _root_set(module: ModuleInfo) -> set:
    """ids of the functions worth evaluating: those containing (at any
    depth — a trigger in a nested def roots the enclosing chain too,
    since the program value may flow in from the outer scope) a call
    into a jitted program, a sharding application, or a `*_scale`
    binding. One pass over the module's calls/assigns, not one walk per
    function."""
    from ray_tpu.tools.lint.rules_donation import (  # noqa: PLC0415
        _binding_from_wrapper_call,
        binding_for_call_ex,
    )

    def mark(node) -> set:
        out = set()
        cur = module.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.add(id(cur))
            cur = module.parent(cur)
        return out

    def imported_program(dotted: Optional[str]) -> bool:
        """A call through a name the symbol table maps to a module-
        level `X = jax.jit(...)` binding in ANOTHER file."""
        project = module.project
        if project is None or not dotted:
            return False
        sym = project.resolve(dotted)
        return (
            sym is not None
            and isinstance(sym.node, ast.Assign)
            and _binding_from_wrapper_call(sym.module, sym.node.value)
            is not None
        )

    roots: set = set()
    for call in module.nodes(ast.Call):
        dotted = module.dotted_name(call.func)
        last = dotted.rsplit(".", 1)[-1] if dotted else ""
        if last in _SHARDING_TRIGGERS or (
            binding_for_call_ex(module, call) is not None
        ) or imported_program(dotted):
            roots |= mark(call)
    for assign in module.nodes(ast.Assign):
        for t in assign.targets:
            name = None
            if isinstance(t, ast.Name):
                name = t.id
            elif isinstance(t, ast.Attribute):
                name = t.attr
            if name is not None and name.endswith("_scale"):
                roots |= mark(assign)
                break
    for fn in module.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        # A scale pool handed in as a PARAMETER pairs it too (the
        # copy_block shape: pools in, pools out).
        if any(
            p.arg.endswith("_scale")
            for p in (*fn.args.posonlyargs, *fn.args.args,
                      *fn.args.kwonlyargs)
        ):
            roots.add(id(fn))
    return roots


def shape_analysis(module: ModuleInfo) -> _Analysis:
    cached = module.memo.get("shape_analysis")
    if cached is not None:
        return cached
    analysis = _Analysis()
    module.memo["shape_analysis"] = analysis
    from ray_tpu.tools.lint.rules_donation import (  # noqa: PLC0415
        binding_for_call_ex,
    )

    root_ids = _root_set(module)
    for fn in module.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        if id(fn) in root_ids:
            _analyze_root(module, fn, analysis, binding_for_call_ex)
    return analysis


def _analyze_root(module, fn, analysis: _Analysis, resolver) -> None:
    interp = Interp(
        module.project,
        jit_resolver=resolver,
    )

    def on_jit_call(call, call_module, def_module, binding, args, kwargs):
        _record_site(analysis, call_module, call, def_module, binding,
                     args)
        if args is None or binding.fn is None:
            return TOP
        mark = len(interp.errors)
        result = interp.eval_jit_body(def_module, binding, args, kwargs)
        body_errors = interp.errors[mark:]
        del interp.errors[mark:]
        fn_name = getattr(binding.fn, "name", "<lambda>")
        for err in body_errors:
            analysis.add(
                analysis.rtl801,
                call,
                f"{err.message} — while abstractly tracing "
                f"{fn_name} ({def_module.relpath}:"
                f"{getattr(err.node, 'lineno', 0)}) with this call "
                "site's shapes",
            )
        _check_donation(
            analysis, call, binding, args, result, fn_name
        )
        return result

    def on_sharding_apply(node, call_module, array, sharding):
        _check_sharding(analysis, node, array, sharding)

    def on_shard_call(node, call_module, program: ShardMapProgram, args):
        if args is None or not isinstance(program.in_specs, tuple):
            return
        mesh = program.mesh
        if not isinstance(mesh, AbstractMesh):
            return
        for arg, spec in zip(args, program.in_specs):
            if isinstance(arg, AbstractArray) and isinstance(
                spec, SpecVal
            ):
                _check_sharding(
                    analysis, node, arg, ShardingVal(mesh, spec)
                )

    interp.on_jit_call = on_jit_call
    interp.on_sharding_apply = on_sharding_apply
    interp.on_shard_call = on_shard_call

    assign_nodes: Dict[str, ast.AST] = {}
    assign_values: Dict[str, List[tuple]] = {}

    def on_assign(mod, stmt, name, value):
        if name.endswith(("_scale", "_cache", "_pool")):
            assign_nodes[name] = stmt
            assign_values.setdefault(name, []).append((stmt, value))

    interp.on_assign = on_assign

    _, frame = interp.eval_root(module, fn)
    _check_pool_pairs(analysis, fn, frame, assign_nodes, assign_values)
    _check_pool_writes(analysis, module, fn)


# ---------------------------------------------------------------------------
# RTL802 — donation
# ---------------------------------------------------------------------------


def _leaf_vs_donated(leaf, donated: AbstractArray) -> Optional[bool]:
    """True: provably aliasable; False: provably NOT; None: unknown."""
    if leaf is None:
        return False
    if isinstance(leaf, (ShardingVal, SpecVal, AbstractMesh, str, bool)):
        return False
    if isinstance(leaf, (int, float, Dim, ElementOf)):
        leaf = AbstractArray(shape=(), dtype=TOP)
    if not isinstance(leaf, AbstractArray):
        return None
    if not isinstance(leaf.shape, tuple):
        return None
    if len(leaf.shape) != len(donated.shape):
        return False
    decided = True
    for a, b in zip(leaf.shape, donated.shape):
        eq = dims_equal(a, b)
        if eq is False:
            return False
        if eq is None:
            decided = False
    if leaf.dtype is TOP:
        decided = False
    elif leaf.dtype != donated.dtype:
        return False
    return True if decided else None


def _check_donation(analysis, call, binding, args, result, fn_name):
    if not binding.donated:
        return
    leaves = flatten_leaves(result)
    if leaves is None or not leaves:
        return
    for pos in sorted(binding.donated):
        if pos >= len(args):
            continue
        value = args[pos]
        if not isinstance(value, AbstractArray):
            continue
        if not shape_fully_known(value.shape) or value.dtype is TOP:
            continue
        any_match = False
        decided = True
        for leaf in leaves:
            st = _leaf_vs_donated(leaf, value)
            if st is True:
                any_match = True
                break
            if st is None:
                decided = False
        if not any_match and decided:
            analysis.add(
                analysis.rtl802,
                call,
                f"argument {pos} is donated but its shape "
                f"{tuple(value.shape)} / dtype {value.dtype} matches "
                f"no output of {fn_name} — XLA cannot alias the "
                "buffer, so donation silently degrades to a copy",
            )


# ---------------------------------------------------------------------------
# RTL803 — sharding divisibility
# ---------------------------------------------------------------------------


def _check_sharding(analysis, node, array, sharding: ShardingVal):
    if not isinstance(array, AbstractArray):
        return
    if not isinstance(array.shape, tuple):
        return
    mesh = sharding.mesh
    spec = sharding.spec
    if not isinstance(mesh, AbstractMesh) or not isinstance(
        spec, SpecVal
    ):
        return
    if not isinstance(mesh.names, tuple):
        return
    entries = spec.entries
    if len(entries) > len(array.shape):
        analysis.add(
            analysis.rtl803,
            node,
            f"PartitionSpec has {len(entries)} entries but the array "
            f"is rank {len(array.shape)}",
        )
        return
    if not isinstance(mesh.sizes, tuple):
        return
    for i, entry in enumerate(entries):
        if entry is None or entry is TOP or not isinstance(
            entry, tuple
        ):
            continue
        total = 1
        for axis in entry:
            size = mesh.axis_size(axis)
            if size is None:
                total = None
                break
            total *= size
        if total is None or total <= 1:
            continue
        dim = array.shape[i]
        if not isinstance(dim, Dim):
            continue
        if dim.divisible_by(total) is False:
            axes = "*".join(entry)
            analysis.add(
                analysis.rtl803,
                node,
                f"dim {i} ({dim!r}) is sharded over mesh axes "
                f"{axes} of total size {total}, which does not divide "
                "it — jax rejects the sharding (or pads, wasting "
                "devices) at mesh scale",
            )


# ---------------------------------------------------------------------------
# RTL804 — paired pools
# ---------------------------------------------------------------------------

_POOL_SUFFIXES = ("_cache", "_pool")


def _unambiguous_array(values, assign_values, name):
    """The ONE abstract array a name denotes, when that is provable:
    the final joined binding if it is an array, else the single
    distinct array among its assignments (a branch assigning None —
    the bf16 arm — joins the final value to TOP but leaves exactly one
    array candidate). Two DIFFERENT array assignments stay ambiguous."""
    final = values.get(name)
    if isinstance(final, AbstractArray):
        return final
    arrs = [
        v for _, v in assign_values.get(name, ())
        if isinstance(v, AbstractArray)
    ]
    distinct = {(repr(a.shape), repr(a.dtype)) for a in arrs}
    if len(distinct) == 1:
        return arrs[0]
    return None


def _check_pool_pairs(
    analysis, fn, frame, assign_nodes, assign_values
) -> None:
    # Final joined bindings: names and self-attrs alike (self tokens
    # are per-class: "self@<relpath>:<Class>").
    values: Dict[str, object] = dict(frame.env)
    for (base, attr), value in frame.attrs.items():
        if base == "self" or base.startswith("self@"):
            values.setdefault(attr, value)
    for sname in set(values) | set(assign_values):
        if not sname.endswith("_scale"):
            continue
        base = sname[: -len("_scale")]
        sval = _unambiguous_array(values, assign_values, sname)
        if sval is None:
            continue
        for suffix in _POOL_SUFFIXES:
            pval = _unambiguous_array(
                values, assign_values, base + suffix
            )
            if pval is None:
                continue
            node = assign_nodes.get(sname) or assign_nodes.get(
                base + suffix
            ) or fn
            if pval.dtype == "int8" and sval.dtype not in FLOAT_DTYPES \
                    and sval.dtype is not TOP:
                analysis.add(
                    analysis.rtl804,
                    node,
                    f"int8 pool {base + suffix} pairs with scale "
                    f"pool {sname} of dtype {sval.dtype}; dequant "
                    "scales must be a float dtype",
                )
            # The shape law holds for ANY quantized pool dtype: pools
            # are stored [..., H*D] and scales [..., H] (per-token
            # per-head), so the ranks agree and every axis but the
            # minor one is the pool's.
            if isinstance(pval.shape, tuple) and isinstance(
                sval.shape, tuple
            ):
                if len(sval.shape) != len(pval.shape):
                    analysis.add(
                        analysis.rtl804,
                        node,
                        f"scale pool {sname} is rank "
                        f"{len(sval.shape)} but the paired pool "
                        f"{base + suffix} is rank "
                        f"{len(pval.shape)}: pools are stored "
                        "[..., H*D] and per-token scales [..., H], "
                        "the same rank",
                    )
                else:
                    for i, (a, b) in enumerate(
                        zip(sval.shape[:-1], pval.shape[:-1])
                    ):
                        if dims_equal(a, b) is False:
                            analysis.add(
                                analysis.rtl804,
                                node,
                                f"scale pool {sname} dim {i} is "
                                f"{a!r} but the paired pool "
                                f"{base + suffix} has {b!r} "
                                "there; scales must mirror "
                                "pool.shape[:-1] on every axis but "
                                "the minor one",
                            )


def _name_of_target(t: ast.AST) -> Optional[str]:
    if isinstance(t, ast.Name):
        return t.id
    if isinstance(t, ast.Attribute) and isinstance(
        t.value, ast.Name
    ) and t.value.id == "self":
        return t.attr
    return None


def _at_write_name(call: ast.Call) -> Optional[str]:
    """`X.at[...].set(...)` / `self.X.at[...].add(...)` -> "X"."""
    if not (
        isinstance(call.func, ast.Attribute)
        and call.func.attr in ("set", "add", "multiply", "min", "max")
        and isinstance(call.func.value, ast.Subscript)
    ):
        return None
    at = call.func.value.value
    if not (isinstance(at, ast.Attribute) and at.attr == "at"):
        return None
    return _name_of_target(at.value)


def _check_pool_writes(analysis, module: ModuleInfo, fn) -> None:
    """Flow form of RTL804: a function owning both X_cache and X_scale
    (params or bindings) that `.at[...]`-writes the pool but never the
    scales leaves stale scales behind — the CoW copy_block hazard."""
    names = {
        p.arg for p in (*fn.args.posonlyargs, *fn.args.args,
                        *fn.args.kwonlyargs)
    }
    writes: Dict[str, ast.Call] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                name = _name_of_target(t)
                if name is not None:
                    names.add(name)
        elif isinstance(node, ast.Call):
            wname = _at_write_name(node)
            if wname is not None:
                writes.setdefault(wname, node)
    for sname in sorted(names):
        if not sname.endswith("_scale"):
            continue
        base = sname[: -len("_scale")]
        for suffix in _POOL_SUFFIXES:
            pname = base + suffix
            if pname not in names:
                continue
            if pname in writes and sname not in writes:
                analysis.add(
                    analysis.rtl804,
                    writes[pname],
                    f"{pname} is written here but its paired scale "
                    f"pool {sname} is never updated in "
                    f"{getattr(fn, 'name', '<fn>')} — a value written "
                    "without its scale is read back at the wrong "
                    "magnitude (block copies must move scales with "
                    "values)",
                )


# ---------------------------------------------------------------------------
# RTL805 — bucket coverage
# ---------------------------------------------------------------------------


def _record_site(analysis, call_module, call, def_module, binding,
                 args) -> None:
    if binding.fn is None or args is None:
        return
    key = (
        def_module.relpath,
        qualname_of(def_module, binding.fn),
    )
    shapes: List[object] = []
    for a in args:
        if isinstance(a, AbstractArray) and isinstance(a.shape, tuple):
            shapes.append(tuple(a.shape))
        else:
            shapes.append(None)
    analysis.sites.append((call_module, call, key, shapes))


def _project_bucket_findings(project) -> List[Tuple]:
    cached = project.memo.get("rtl805_findings")
    if cached is not None:
        return cached
    # The site sweep is ALWAYS project-wide, even on --changed runs:
    # a checked module's width may only be provably uncovered against a
    # bucket table that lives in an unchecked module, and the baseline
    # stale/orphan bookkeeping assumes a checked file's findings are
    # reproducible. (Findings still only SURFACE in checked modules —
    # rule.check runs per checked module and filters by path.)
    sites: List[tuple] = []
    for module in project.modules:
        sites.extend(shape_analysis(module).sites)
    by_prog: Dict[tuple, List[tuple]] = {}
    seen_nodes: set = set()
    for site in sites:
        dedup = (id(site[1]), site[2], repr(site[3]))
        if dedup in seen_nodes:
            continue
        seen_nodes.add(dedup)
        by_prog.setdefault(site[2], []).append(site)
    findings: List[Tuple] = []
    emitted: set = set()

    def emit(module, node, message):
        key = (id(node), message)
        if key not in emitted:
            emitted.add(key)
            findings.append((module, node, message))

    for key, prog_sites in by_prog.items():
        max_args = max(len(s[3]) for s in prog_sites)
        for argpos in range(max_args):
            shaped = [
                s for s in prog_sites
                if argpos < len(s[3]) and s[3][argpos] is not None
            ]
            ranks = {len(s[3][argpos]) for s in shaped}
            if len(ranks) != 1:
                continue
            (rank,) = ranks
            for dimpos in range(rank):
                entries = []
                for s in shaped:
                    dim = s[3][argpos][dimpos]
                    if isinstance(dim, ElementOf):
                        entries.append((s, dim.values, True))
                    elif isinstance(dim, Dim) and dim.is_const and (
                        dim.const_value >= 0
                    ):
                        entries.append((s, {dim.const_value}, False))
                tables = [e for e in entries if e[2]]
                if not tables:
                    continue
                union = set()
                for t in tables:
                    union |= t[1]
                for s, vals, is_table in entries:
                    if not is_table and not vals <= union:
                        (w,) = vals
                        emit(
                            s[0], s[1],
                            f"argument {argpos} dim {dimpos} feeds "
                            f"width {w} to {key[1]} but the "
                            "statically-resolved bucket table only "
                            f"covers {sorted(union)} — no bucket "
                            "program matches this shape, so it cold-"
                            "compiles under live traffic",
                        )
                for i, (s1, v1, _) in enumerate(tables):
                    for s2, v2, _ in tables[i + 1:]:
                        if not v1 <= v2 and not v2 <= v1:
                            later = max(
                                (s1, s2),
                                key=lambda s: (
                                    s[0].relpath,
                                    getattr(s[1], "lineno", 0),
                                ),
                            )
                            emit(
                                later[0], later[1],
                                f"argument {argpos} dim {dimpos} of "
                                f"{key[1]} is driven by two different "
                                f"bucket tables ({sorted(v1)} vs "
                                f"{sorted(v2)}) — warmup and the live "
                                "path have drifted, so some widths "
                                "cold-compile under traffic",
                            )
    project.memo["rtl805_findings"] = findings
    return findings


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


class _ShapeRule(Rule):
    family = "shapes"
    bucket = ""

    def check(self, module: ModuleInfo) -> List[Finding]:
        analysis = shape_analysis(module)
        return [
            self.finding(module, node, message)
            for node, message in getattr(analysis, self.bucket)
        ]


class JitCallShapeMismatchRule(_ShapeRule):
    id = "RTL801"
    name = "jit-call-shape-mismatch"
    bucket = "rtl801"
    description = (
        "caller's abstract shapes hit a provable geometry contradiction "
        "inside the jitted program they are fed to"
    )
    rationale = (
        "a shape mismatch between a call site and the traced body "
        "surfaces as an XLA compile error at best — on a warm serving "
        "path it means a retrace, a perf cliff, or garbage read through "
        "a mis-sized buffer. The abstract interpreter pushes the "
        "caller's (possibly symbolic) shapes through the body's "
        "reshape/matmul/concatenate/indexing ops and reports only "
        "contradictions that hold for EVERY assignment of the symbols; "
        "any unknown stays silent."
    )
    bad_example = """
        import jax
        import jax.numpy as jnp

        def step(x, w):
            return x @ w

        def run():
            f = jax.jit(step)
            x = jnp.zeros((4, 8))
            w = jnp.zeros((4, 16))  # contraction dim is 8, not 4
            return f(x, w)
    """
    good_example = """
        import jax
        import jax.numpy as jnp

        def step(x, w):
            return x @ w

        def run():
            f = jax.jit(step)
            x = jnp.zeros((4, 8))
            w = jnp.zeros((8, 16))
            return f(x, w)
    """


class DonationAliasMismatchRule(_ShapeRule):
    id = "RTL802"
    name = "donation-alias-mismatch"
    bucket = "rtl802"
    description = (
        "donated buffer provably aliases no output (shape or dtype "
        "mismatch): donation degrades to a copy"
    )
    rationale = (
        "donate_argnums only helps when XLA can reuse the donated "
        "buffer for an output of identical shape AND dtype. When none "
        "matches, jax silently copies — the donation is dead weight and "
        "peak memory is what it would be without it, which at pool "
        "sizes (the paged KV cache) is the difference between fitting "
        "and OOMing. The rule fires only when every output's geometry "
        "is statically known and provably different from the donated "
        "buffer's."
    )
    bad_example = """
        import jax
        import jax.numpy as jnp

        def step(buf, x):
            return (buf + x).astype(jnp.bfloat16)

        def run():
            f = jax.jit(step, donate_argnums=(0,))
            buf = jnp.zeros((128, 64), jnp.float32)
            x = jnp.zeros((128, 64), jnp.float32)
            return f(buf, x)
    """
    good_example = """
        import jax
        import jax.numpy as jnp

        def step(buf, x):
            return buf + x

        def run():
            f = jax.jit(step, donate_argnums=(0,))
            buf = jnp.zeros((128, 64), jnp.float32)
            x = jnp.zeros((128, 64), jnp.float32)
            return f(buf, x)
    """


class ShardingNondivisibleRule(_ShapeRule):
    id = "RTL803"
    name = "sharding-nondivisible"
    bucket = "rtl803"
    description = (
        "PartitionSpec shards a dim over mesh axes whose size does not "
        "divide it"
    )
    rationale = (
        "a mesh axis of size 4 sharding a dim of 9 either trace-fails "
        "or (through uneven-sharding paths) pads and silently wastes "
        "devices. The hazard appears exactly when the mesh refactor "
        "lands: PartitionSpecs written against one mesh shape break on "
        "the next. Mesh axis names AND sizes resolve statically "
        "(literal Mesh(...), create_device_mesh((2, 4)), cross-module "
        "constants) and the rule checks divisibility symbolically — "
        "`2*B+1` is provably odd whatever B is."
    )
    bad_example = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        def place():
            mesh = Mesh(
                mesh_utils.create_device_mesh((2, 4)), ("dp", "tp")
            )
            x = jnp.zeros((9, 32))  # 2 does not divide 9
            return jax.device_put(x, NamedSharding(mesh, P("dp", "tp")))
    """
    good_example = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        def place():
            mesh = Mesh(
                mesh_utils.create_device_mesh((2, 4)), ("dp", "tp")
            )
            x = jnp.zeros((8, 32))
            return jax.device_put(x, NamedSharding(mesh, P("dp", "tp")))
    """


class PairedPoolGeometryRule(_ShapeRule):
    id = "RTL804"
    name = "paired-pool-geometry"
    bucket = "rtl804"
    description = (
        "int8 K/V pool whose scale pool breaks the shared-leading-axes "
        "law, is not float, or is skipped on a pool write"
    )
    rationale = (
        "int8 pools store per-token per-head scales in a mirror pool of "
        "the same rank and leading axes ([L, N, bs, H] against the "
        "lane-dense [L, N, bs, H*D]). A scale pool with the wrong geometry scatters garbage "
        "scales; an int dtype truncates them; and a block write or "
        "copy (CoW copy_block) that moves values without scales reads "
        "back at the wrong magnitude — all silent numeric corruption, "
        "not crashes. The pairing is by name (X_cache/X_pool with "
        "X_scale), the same convention the runner uses."
    )
    bad_example = """
        import jax.numpy as jnp

        def build_pools(num_blocks, block_size, heads, head_dim):
            blocks = (4, num_blocks, block_size)
            k_cache = jnp.zeros(blocks + (heads * head_dim,), jnp.int8)
            k_scale = jnp.zeros(blocks[:2] + (heads,), jnp.bfloat16)
            return k_cache, k_scale
    """
    good_example = """
        import jax.numpy as jnp

        def build_pools(num_blocks, block_size, heads, head_dim):
            blocks = (4, num_blocks, block_size)
            k_cache = jnp.zeros(blocks + (heads * head_dim,), jnp.int8)
            k_scale = jnp.zeros(blocks + (heads,), jnp.bfloat16)
            return k_cache, k_scale
    """


class BucketCoverageDriftRule(_ShapeRule):
    id = "RTL805"
    name = "bucket-coverage-drift"
    bucket = "rtl805"
    description = (
        "shape fed to a bucketed jitted program that no entry of the "
        "statically-resolved bucket table covers (guaranteed cold "
        "compile)"
    )
    rationale = (
        "bucketed programs keep XLA's compiled-program count O(1): "
        "warmup compiles one program per table entry, and the live "
        "path pads every shape to an entry. A width outside the table "
        "— or two call sites driven by two different tables — is a "
        "guaranteed cold compile under live traffic: multi-second "
        "latency spikes the flight recorder can only blame after the "
        "fact. The table resolves statically (a constant tuple driving "
        "a warmup loop or a bucket_for-style lookup); unknown widths "
        "stay silent."
    )
    bad_example = """
        import jax
        import jax.numpy as jnp

        BUCKETS = (8, 16, 32)

        def bucket_for(n):
            for b in BUCKETS:
                if b >= n:
                    return b
            raise ValueError(n)

        def step(tokens):
            return tokens

        def run(n):
            f = jax.jit(step)
            for b in BUCKETS:
                f(jnp.zeros((1, b), jnp.int32))  # warmup: 8/16/32
            f(jnp.zeros((1, 24), jnp.int32))  # 24 is not a bucket
    """
    good_example = """
        import jax
        import jax.numpy as jnp

        BUCKETS = (8, 16, 32)

        def bucket_for(n):
            for b in BUCKETS:
                if b >= n:
                    return b
            raise ValueError(n)

        def step(tokens):
            return tokens

        def run(n):
            f = jax.jit(step)
            for b in BUCKETS:
                f(jnp.zeros((1, b), jnp.int32))  # warmup: 8/16/32
            f(jnp.zeros((1, bucket_for(n)), jnp.int32))
    """

    def check(self, module: ModuleInfo) -> List[Finding]:
        project = module.project
        if project is None:
            shape_analysis(module)
            return []
        findings = _project_bucket_findings(project)
        return [
            self.finding(module, node, message)
            for fmod, node, message in findings
            if fmod is module
        ]


RULES = [
    JitCallShapeMismatchRule,
    DonationAliasMismatchRule,
    ShardingNondivisibleRule,
    PairedPoolGeometryRule,
    BucketCoverageDriftRule,
]
