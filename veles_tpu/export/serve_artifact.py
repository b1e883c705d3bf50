"""AOT serving artifacts: pre-exported decode programs in a package.

The cold-start closer of ROADMAP item 3 (reference analog:
``Workflow.package_export`` → ``libVeles/src/workflow_loader.cc``
consuming pre-built units instead of re-deriving them): the serving
engine's whole program surface — one prefill per bucket plus the ONE
fixed-shape decode step — is serialized through ``jax.export`` into a
package directory:

    <pkg>/contents.json           format_version 3 with a "serving"
                                  block: knobs, abstract input
                                  signature, program file table
    <pkg>/serve_prefill_<B>.bin   jax.export artifact per bucket
    <pkg>/serve_decode.bin        the fixed-shape decode step

``ContinuousEngine`` loads the artifact at :meth:`start` and installs
the deserialized programs straight into its program cache, so serving
performs ZERO jit traces/compiles (parameters stay runtime arguments
— the artifact is valid across checkpoints, training between bursts
included; only shape/knob/quant-policy changes invalidate it, which
the stamped signature catches at load).

Produce with ``veles-tpu export serve-artifact MODEL.py --out DIR``;
consume with ``--serve-artifact DIR`` (or
``root.common.serving.artifact``). A corrupt or mismatched artifact
falls back to live jit with a counted warning — never an outage.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

from ..error import VelesError

#: bumped when the serving-block layout or program calling convention
#: changes; readers refuse newer artifacts instead of guessing.
#: v2: the paged KV cache — prefill takes the slot's page-table row,
#: the decode step takes the (slots, pages_per_slot) page tables plus
#: a per-row advance mask, and the pool buffers are page-shaped; v1
#: artifacts fail the signature check and fall back to live jit.
#: v3: the prefix-sharing request plane — the decode step takes a
#: per-slot shared-page count whose write-back masks adopted prefix
#: pages to the sink (signature also stamps the prefix_cache /
#: prefill_chunk knobs); v2 artifacts fail the signature check and
#: fall back to live jit.
#: v4: the O(1)-state serving lane — recurrent stacks export the
#: chunk-scan ("rscan") and recurrent decode ("rstep") programs whose
#: pool is per-slot STATE tensors instead of paged KV (signature kind
#: "recurrent" stamps the state leaf shapes); paged artifacts are
#: unchanged, so v3 paged artifacts still load
#: v5: tensor-parallel serving — the signature stamps the mesh-slice
#: width ("tp") and axis layout ("mesh"), and under tp>1 the exported
#: programs are shard_mapped over the ("model",) mesh (a load needs
#: the same device count). Every v4 artifact lacks the tp keys, so it
#: refuses on the signature check and falls back counted to live jit
#: — never an outage
#: v6: the decode step keeps its tokens on the device — it takes the
#: tokens it gave in the call before back as they are (and a host row
#: that says -1 wherever their last row holds; signature key
#: "step_tokens"); every v5 artifact lacks the key, refuses on the
#: signature check and falls back counted to live jit
ARTIFACT_VERSION = 6


def _specs_of(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def export_serve_artifact(workflow, path: str,
                          max_slots: Optional[int] = None,
                          buckets=None,
                          max_context: Optional[int] = None,
                          decode_block: Optional[int] = None,
                          page_size: Optional[int] = None,
                          pages: Optional[int] = None,
                          quant_weights: Optional[bool] = None,
                          quant_kv: Optional[bool] = None) -> str:
    """Export the continuous engine's programs for ``workflow`` into
    the package directory ``path``. Knobs default exactly like
    ``GenerationAPI`` (``root.common.serving.*`` /
    ``root.common.quant.*``), so an artifact exported with the same
    config a server will boot with is guaranteed to match its
    signature."""
    import jax
    import jax.numpy as jnp
    from jax import export as jexport
    from ..config import root
    from ..serving.engine import ContinuousEngine

    serving_cfg = root.common.serving
    knobs = {
        "max_slots": int(max_slots if max_slots is not None
                         else serving_cfg.get("max_slots", 8)),
        "max_context": int(max_context if max_context is not None
                           else serving_cfg.get("max_context", 640)),
        "decode_block": int(decode_block if decode_block is not None
                            else serving_cfg.get("decode_block", 1)),
    }
    try:
        engine = ContinuousEngine(
            workflow,
            buckets=(buckets if buckets is not None
                     else serving_cfg.get("buckets",
                                          [16, 32, 64, 128])),
            page_size=page_size, pages=pages,
            quant_weights=quant_weights, quant_kv=quant_kv,
            name="serve_artifact_export", **knobs)
    except VelesError:
        # not a transformer LM chain — a recurrent stack (Embedding →
        # LSTM/SSM → LMHead) exports the O(1)-state lane's two
        # programs instead, same fallback order as GenerationAPI
        from ..serving.recurrent import RecurrentEngine
        return _export_recurrent(
            RecurrentEngine(workflow, page_size=page_size,
                            name="serve_artifact_export", **knobs),
            workflow, path)
    signature = engine.stack_signature()
    params = engine._prepare_params()
    engine._ensure_pool(params)
    params_spec = _specs_of(params)
    caches_spec = _specs_of(engine._caches)
    slots = engine.max_slots
    keys_spec = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    seed_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    table_row_spec = jax.ShapeDtypeStruct((engine.pages_per_slot,),
                                          jnp.int32)
    tables_spec = jax.ShapeDtypeStruct(
        (slots, engine.pages_per_slot), jnp.int32)
    svec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)

    os.makedirs(path, exist_ok=True)
    programs: Dict[str, str] = {}
    for bucket in engine.buckets:
        exported = jexport.export(engine._build_prefill(bucket))(
            params_spec,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            i32, i32, f32, seed_spec, table_row_spec, keys_spec,
            caches_spec)
        fname = "serve_prefill_%d.bin" % bucket
        with open(os.path.join(path, fname), "wb") as fout:
            fout.write(exported.serialize())
        programs["prefill_%d" % bucket] = fname
    exported = jexport.export(engine._build_decode())(
        params_spec, svec, svec,
        jax.ShapeDtypeStruct((slots,), jnp.float32),
        svec, tables_spec, svec, _specs_of(engine._last), keys_spec,
        caches_spec)
    with open(os.path.join(path, "serve_decode.bin"), "wb") as fout:
        fout.write(exported.serialize())
    programs["decode"] = "serve_decode.bin"

    from .package import required_format_version
    contents = {
        # the serving block is a v3 feature: v2 readers must refuse
        # rather than silently ignore the programs they came for
        "format_version": required_format_version(serving=True),
        "workflow": workflow.name,
        "checksum": workflow.checksum(),
        # program-only package: params stay RUNTIME inputs (the
        # artifact survives further training), so no unit tensors ride
        # along — package_import still reads it (empty unit list)
        "units": [],
        "serving": {
            "artifact_version": ARTIFACT_VERSION,
            "jax_version": jax.__version__,
            "signature": signature,
            "programs": programs,
        },
    }
    with open(os.path.join(path, "contents.json"), "w") as fout:
        json.dump(contents, fout, indent=2)
    return path


def _export_recurrent(engine, workflow, path: str) -> str:
    """Export the O(1)-state lane's program pair: the ``page_size``-
    token chunk scan (``rscan``) and the recurrent decode step
    (``rstep``). The pool inputs are the engine's per-slot state
    pytree — fixed shapes whatever the context, which is exactly why
    this artifact stays valid for any prompt length."""
    import jax
    import jax.numpy as jnp
    from jax import export as jexport
    signature = engine.stack_signature()
    from ..nn.sampling import params_of
    params = params_of(workflow)
    engine._ensure_pool(params)
    params_spec = _specs_of(params)
    states_spec = _specs_of(engine._states)
    slots = engine.max_slots
    keys_spec = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    seed_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    svec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)

    os.makedirs(path, exist_ok=True)
    programs: Dict[str, str] = {}
    exported = jexport.export(engine._build_scan_chunk())(
        params_spec,
        jax.ShapeDtypeStruct((engine.page_size,), jnp.int32),
        i32, i32, f32, seed_spec, i32, keys_spec, states_spec)
    with open(os.path.join(path, "serve_rscan.bin"), "wb") as fout:
        fout.write(exported.serialize())
    programs["rscan"] = "serve_rscan.bin"
    exported = jexport.export(engine._build_decode())(
        params_spec, svec,
        jax.ShapeDtypeStruct((slots,), jnp.float32),
        svec, keys_spec, states_spec)
    with open(os.path.join(path, "serve_rstep.bin"), "wb") as fout:
        fout.write(exported.serialize())
    programs["rstep"] = "serve_rstep.bin"

    from .package import required_format_version
    contents = {
        "format_version": required_format_version(serving=True),
        "workflow": workflow.name,
        "checksum": workflow.checksum(),
        "units": [],
        "serving": {
            "artifact_version": ARTIFACT_VERSION,
            "jax_version": jax.__version__,
            "signature": signature,
            "programs": programs,
        },
    }
    with open(os.path.join(path, "contents.json"), "w") as fout:
        json.dump(contents, fout, indent=2)
    return path


def load_serve_programs(path: str, expect_signature: Dict
                        ) -> Dict[Tuple[str, Optional[int]], object]:
    """Read an artifact directory and deserialize every program. The
    stored abstract signature must equal ``expect_signature`` (the
    loading engine's knobs, quant policy and parameter/pool specs) —
    shape-committed programs must never run on reinterpreted buffers.
    Raises :class:`VelesError` on ANY problem; the engine converts
    that into its counted live-jit fallback."""
    from jax import export as jexport
    contents_path = os.path.join(path, "contents.json")
    try:
        with open(contents_path) as fin:
            contents = json.load(fin)
    except (OSError, ValueError) as e:
        raise VelesError("serve-artifact %s unreadable: %s"
                         % (contents_path, e)) from e
    serving = contents.get("serving")
    if not isinstance(serving, dict):
        raise VelesError(
            "package %s carries no serving block (format_version %s) — "
            "not a serve-artifact" % (path,
                                      contents.get("format_version")))
    version = int(serving.get("artifact_version", 0))
    if version > ARTIFACT_VERSION:
        raise VelesError(
            "serve-artifact version %d is newer than this reader (%d)"
            % (version, ARTIFACT_VERSION))
    stored = json.dumps(serving.get("signature"), sort_keys=True)
    expected = json.dumps(expect_signature, sort_keys=True)
    if stored != expected:
        raise VelesError(
            "serve-artifact %s was exported for a different "
            "model/knob/quant configuration — re-export it "
            "(veles-tpu export serve-artifact)" % path)
    programs: Dict[Tuple[str, Optional[int]], object] = {}
    for label, fname in serving.get("programs", {}).items():
        try:
            with open(os.path.join(path, fname), "rb") as fin:
                blob = fin.read()
            exported = jexport.deserialize(bytearray(blob))
        except Exception as e:      # noqa: BLE001 — one fallback path
            raise VelesError("serve-artifact program %s corrupt: %s: %s"
                             % (fname, type(e).__name__, e)) from e
        if label == "decode":
            # the paged step is keyed by its view length in pages,
            # and the artifact holds the whole view alone
            key = ("step", expect_signature["pages_per_slot"])
        elif label.startswith("prefill_"):
            key = ("prefill", int(label[len("prefill_"):]))
        elif label == "rscan":
            # O(1)-state lane (v4): the chunked prefill scan
            key = ("scan", None)
        elif label == "rstep":
            key = ("step", None)
        else:
            raise VelesError("serve-artifact %s: unknown program "
                             "label %r" % (path, label))
        programs[key] = exported.call
    if expect_signature.get("kind") == "recurrent":
        want = {("scan", None), ("step", None)}
    else:
        want = {("prefill", b)
                for b in expect_signature.get("buckets", ())}
        want.add(("step", expect_signature["pages_per_slot"]))
    missing = want - set(programs)
    if missing:
        raise VelesError("serve-artifact %s is missing programs: %s"
                         % (path, sorted(missing)))
    return programs
