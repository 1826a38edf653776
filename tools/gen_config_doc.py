"""Generate docs/CONFIG.md from the Config dataclass tree.

The ds_config compatibility reference a migrating DeepSpeed user needs:
every supported key path, its type, and its default — introspected from
``deepspeed_tpu.config.config.Config`` so the document can never drift
from the code. Re-run after config changes:

    python tools/gen_config_doc.py
"""

from __future__ import annotations

import dataclasses
import os
import sys
import typing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from deepspeed_tpu.config.config import Config  # noqa: E402

HEADER = """# ds_config key reference

Every key `deepspeed_tpu.initialize(config=...)` understands, with types
and defaults — the same JSON schema as the reference's ds_config
(`\"auto\"` is accepted wherever the reference accepts it; batch keys
resolve against each other and the data-parallel world size). Generated
by `tools/gen_config_doc.py` from the typed config tree
(`deepspeed_tpu/config/config.py`); do not edit by hand.

Keys the reference has that are intentionally absent here (CUDA-specific
allocator/stream tuning, `amp`, `comms_config` torch-backend options)
are collapsed by the TPU design: XLA owns scheduling/fusion and there is
one backend. `optimizer.params` / `scheduler.params` accept the
reference's per-optimizer and per-scheduler key sets (see
`ops/optimizers.py` / `runtime/lr_schedules.py`), plus the TPU extension
`optimizer.params.moment_dtype: "bfloat16"` (compact chip-resident Adam
moments).

"""


def _type_name(t) -> str:
    origin = typing.get_origin(t)
    if origin is typing.Union:
        args = [a for a in typing.get_args(t) if a is not type(None)]
        inner = " | ".join(_type_name(a) for a in args)
        return (inner + " | null") if len(typing.get_args(t)) > len(args) \
            else inner
    if origin in (dict, typing.Dict):
        return "object"
    if origin in (list, typing.List):
        return "array"
    return getattr(t, "__name__", str(t)).replace("NoneType", "null")


def walk(cls, prefix: str, rows: list) -> None:
    hints = typing.get_type_hints(cls)
    for f in dataclasses.fields(cls):
        if f.name.isupper() or f.name.startswith("_"):
            continue
        t = hints.get(f.name, f.type)
        key = f"{prefix}{f.name}"
        if dataclasses.is_dataclass(t):
            rows.append((key, "section", ""))
            walk(t, key + ".", rows)
            continue
        if f.default is not dataclasses.MISSING:
            default = f.default
        elif f.default_factory is not dataclasses.MISSING:  # type: ignore
            default = f.default_factory()                   # type: ignore
        else:
            default = ""
        rows.append((key, _type_name(t), repr(default)))


SERVING_HEADER = """

## Ragged serving config (`RaggedInferenceConfig`)

Keys of `deepspeed_tpu.inference.v2.RaggedInferenceConfig` — the v2
ragged engine's constructor config (`InferenceEngineV2` /
`build_hf_engine(engine_config=...)`), the analogue of the reference's
`RaggedInferenceEngineConfig`. See docs/serving.md for the serving guide
(tensor-parallel sharding map, comm accounting, per-chip KV formula).

"""


ENV_HEADER = """

## Environment knobs (`DSTPU_*`)

Every `DSTPU_*` environment variable the code reads — name, default and
reading site — generated from an AST scan of `deepspeed_tpu/`,
`tools/`, `bin/` and `examples/`
(`tools/dslint scan_env_knobs`). `bin/dstpu_lint`'s DSL004/DSL005
rules fail CI when this table and the code drift, so re-run
`python tools/gen_config_doc.py` after adding or removing a knob.
"(required)" means the knob is read with no default
(`os.environ[...]` or a presence test); "(dynamic)" means the default
is computed at the read site.

"""


def _env_table(reads) -> list:
    by_name: dict = {}
    for r in reads:
        by_name.setdefault(r.name, []).append(r)
    out = ["| knob | default | read at |", "|---|---|---|"]
    for name in sorted(by_name):
        sites = by_name[name]
        defaults = []
        for r in sites:
            d = r.default if r.default is not None else "(required)"
            if d not in defaults:
                defaults.append(d)
        dcol = " / ".join(defaults).replace("|", "\\|")
        # file-level sites only: line numbers rot on every unrelated
        # edit and the drift rules compare names, not lines
        files = []
        for r in sites:
            if r.path not in files:
                files.append(r.path)
        scol = ", ".join(f"`{p}`" for p in files[:3])
        if len(files) > 3:
            scol += f" (+{len(files) - 3} more)"
        out.append(f"| `{name}` | {dcol} | {scol} |")
    return out


def _table(rows: list) -> list:
    out = ["| key | type | default |", "|---|---|---|"]
    for key, tname, default in rows:
        if tname == "section":
            out.append(f"| **`{key}`** | — | — |")
        else:
            d = default.replace("|", "\\|")
            t = tname.replace("|", "\\|")
            out.append(f"| `{key}` | {t} | `{d}` |")
    return out


def main():
    from deepspeed_tpu.inference.v2.config import RaggedInferenceConfig
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dslint import scan_env_knobs
    rows: list = []
    walk(Config, "", rows)
    srows: list = []
    walk(RaggedInferenceConfig, "", srows)
    knobs = scan_env_knobs(REPO)
    out = [HEADER] + _table(rows) + [SERVING_HEADER] + _table(srows) \
        + [ENV_HEADER] + _env_table(knobs)
    os.makedirs(os.path.join(REPO, "docs"), exist_ok=True)
    path = os.path.join(REPO, "docs", "CONFIG.md")
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"wrote {path} ({len(rows)} + {len(srows)} keys, "
          f"{len({k.name for k in knobs})} env knobs)")


if __name__ == "__main__":
    main()
