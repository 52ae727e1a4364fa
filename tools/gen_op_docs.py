"""Generate docs/api_ops.md from the live operator registry (parity:
the reference auto-generates python docstrings/signatures from each
op's dmlc::Parameter schema at import; here the same declarative Arg
schemas drive a browsable API reference).

    JAX_PLATFORMS=cpu python tools/gen_op_docs.py
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"  # needs no chip: set before jax loads

import mxnet_tpu  # noqa: E402,F401 — populates the registry
from mxnet_tpu.ops.registry import OP_ALIASES, OP_REGISTRY  # noqa: E402


def arg_row(a):
    typ = a.type if isinstance(a.type, str) else \
        getattr(a.type, "__name__", str(a.type)) if a.type else "any"
    dfl = "required" if a.required else repr(a.default)
    doc = (a.doc or "").replace("|", "\\|").replace("\n", " ")
    return "| `%s` | %s | %s | %s |" % (a.name, typ, dfl, doc)


def main():
    ops = {n: o for n, o in OP_REGISTRY.items() if not n.startswith("_")}
    internal = {n: o for n, o in OP_REGISTRY.items() if n.startswith("_")}
    aliases = {}
    for alias, target in sorted(OP_ALIASES.items()):
        aliases.setdefault(target, []).append(alias)

    lines = [
        "# Operator API reference",
        "",
        "Auto-generated from the live registry by `tools/gen_op_docs.py`"
        " — regenerate after adding ops.  Every operator is callable as"
        " `mx.nd.<name>` (eager) and `mx.sym.<name>` (symbolic); the"
        " declarative `Arg` schemas below are the same ones that power"
        " parameter validation and the autogen bindings (the reference"
        " generated these surfaces from dmlc::Parameter).",
        "",
        "%d public operators, %d internal (`_`-prefixed), %d aliases."
        % (len(ops), len(internal), len(OP_ALIASES)),
        "",
    ]
    for name in sorted(ops):
        op = ops[name]
        lines.append("## `%s`" % name)
        extra = []
        if aliases.get(name):
            extra.append("aliases: %s" %
                         ", ".join("`%s`" % a for a in aliases[name]))
        if op.input_names:
            extra.append("inputs: %s" %
                         ", ".join("`%s`" % i for i in op.input_names))
        if op.num_outputs != 1:
            extra.append("outputs: %s" % op.num_outputs)
        if op.needs_rng:
            extra.append("stochastic (consumes a PRNG stream)")
        if op.takes_is_train:
            extra.append("train/inference mode dependent")
        if extra:
            lines.append("*" + "; ".join(extra) + "*")
        if op.docstring:
            lines.append("")
            lines.append(op.docstring.strip())
        args = [a for a in op.schema.args.values()]
        if args:
            lines += ["", "| arg | type | default | doc |",
                      "|---|---|---|---|"]
            lines += [arg_row(a) for a in args]
        lines.append("")

    lines += ["## Internal operators", "",
              "Backward/internal registrations (`_`-prefixed), reachable "
              "through autograd or frontend helpers:", "",
              ", ".join("`%s`" % n for n in sorted(internal)), ""]

    out = (sys.argv[1] if len(sys.argv) > 1
           else os.path.join(REPO, "docs", "api_ops.md"))
    with open(out, "w") as f:
        f.write("\n".join(lines))
    print("wrote %s (%d public ops, %d KB)"
          % (out, len(ops), os.path.getsize(out) // 1024))


if __name__ == "__main__":
    main()
