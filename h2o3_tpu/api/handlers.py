"""v3 endpoint implementations.

Reference: ``water/api/RegisterV3Api.java`` route inventory (SURVEY.md
Appendix B) and the per-group handlers (``FramesHandler``,
``ParseHandler``, ``ModelBuilderHandler``, ``RapidsHandler``,
``JobsHandler``, ``GridSearchHandler``, ``CloudHandler`` ...).  Response
shapes follow the ``api/schemas3`` objects (FrameV3, ModelSchemaV3, JobV3,
CloudV3, H2OErrorV3) closely enough for thin clients to port.
"""

from __future__ import annotations

import dataclasses
import io
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from h2o3_tpu import __version__
from h2o3_tpu.api.registry import algo_map
from h2o3_tpu.api.server import H2OServer, RequestServer, RestError
from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.frame.parse import parse_csv, parse_setup
from h2o3_tpu.keyed import DKV
from h2o3_tpu.models.framework import Job, Model
from h2o3_tpu.models.grid import Grid, GridSearch, SearchCriteria
from h2o3_tpu.rapids import Session, exec_rapids


class _RawFile:
    """An imported-but-unparsed source (reference: raw ByteVec under a key).
    Keeps the ORIGINAL bytes (a multi-entry zip must reach the parser
    whole); name/data expose the first decompressed part for sniffing."""

    def __init__(self, path: str, text: Optional[str] = None,
                 data: Optional[bytes] = None) -> None:
        from h2o3_tpu.frame.ingest import _decompress

        self.path = path
        if text is not None:
            self.raw_name, self.raw_data = path, text.encode()
        else:
            self.raw_name = os.path.basename(path) or path
            self.raw_data = data or b""
        self.name, self.data = _decompress(self.raw_name, self.raw_data)

    @property
    def text(self) -> str:
        return self.data.decode("utf-8", errors="replace")


_SESSIONS: Dict[str, Session] = {}


# ---------------------------------------------------------------------------
# helpers


def _get_frame(frame_id: str) -> Frame:
    fr = DKV.get(frame_id)
    if not isinstance(fr, Frame):
        fr = _dist_frame_from_ring(frame_id)
        if fr is None:
            raise RestError(404, f"frame {frame_id!r} not found")
    return fr


def _dist_frame_from_ring(frame_id: str) -> Optional[Frame]:
    """A chunk-homed frame resolved from the DKV ring: any member whose
    local registry misses the key can still serve (or fit against) a
    frame parsed to homes elsewhere in the cloud — the layout and parse
    setup live beside the chunks at MAX_REPLICAS depth."""
    from h2o3_tpu.cluster import active_cloud

    cloud = active_cloud()
    store = getattr(cloud, "dkv_store", None) if cloud is not None else None
    if store is None:
        return None
    from h2o3_tpu.cluster import frames as _frames

    try:
        layout = store.get(_frames.layout_key(frame_id))
        if not isinstance(layout, dict):
            return None
        setup = store.get(_frames.setup_key(frame_id))
        if setup is None:
            return None
        return _frames.DistFrame(
            layout, _frames.setup_from_payload(setup), store)
    except Exception:
        return None


def _get_model(model_id: str) -> Model:
    m = DKV.get(model_id)
    if not isinstance(m, Model):
        raise RestError(404, f"model {model_id!r} not found")
    return m


def _frame_schema(fr: Frame, key: str, rows: int = 10) -> Dict[str, Any]:
    """FrameV3 / FrameBaseV3 (api/schemas3/FrameV3.java)."""
    cols = []
    for c in fr.columns:
        r = c.rollups if c.type in (ColType.NUM, ColType.TIME, ColType.CAT) else None
        head = c.data[:rows]
        if c.type is ColType.CAT:
            data = [c.domain[v] if v >= 0 else None for v in head]
        elif c.type is ColType.STR:
            data = [None if v is None else str(v) for v in head]
        else:
            data = [None if np.isnan(v) else float(v) for v in head]
        cols.append(
            {
                "label": c.name,
                "type": c.type.name.lower(),
                "domain": c.domain,
                "domain_cardinality": len(c.domain) if c.domain else 0,
                "missing_count": int(r.na_count) if r else int(c.na_count()),
                "mins": [r.min] if r else [],
                "maxs": [r.max] if r else [],
                "mean": r.mean if r else None,
                "sigma": r.sigma if r else None,
                "data": data,
            }
        )
    return {
        "frame_id": {"name": key},
        "rows": fr.nrows,
        "num_columns": fr.ncols,
        "column_names": fr.names,
        "columns": cols,
    }


def _job_schema(job: Job) -> Dict[str, Any]:
    """JobV3 (api/schemas3/JobV3.java)."""
    return {
        "key": {"name": job.key},
        "description": job.description,
        "status": job.status,
        "progress": job.progress,
        "progress_msg": getattr(job, "progress_msg", None),
        "msec": int(job.run_time * 1000),
        "exception": str(job.exception) if job.exception else None,
        "dest": getattr(job, "dest", None),
    }


def _metrics_schema(mm: Any) -> Optional[Dict[str, Any]]:
    if mm is None:
        return None
    if isinstance(mm, dict):  # e.g. isolation forest's {mean_score, max_score}
        return {k: (None if isinstance(v, float) and np.isnan(v) else v)
                for k, v in mm.items() if np.isscalar(v)}
    out = {}
    for k in (
        "mse rmse mae rmsle r2 mean_residual_deviance auc pr_auc gini logloss "
        "mean_per_class_error max_f1_threshold nobs"
    ).split():
        v = getattr(mm, k, None)
        if v is not None and np.isscalar(v):
            out[k] = None if isinstance(v, float) and np.isnan(v) else v
    return out


def _model_schema(m: Model) -> Dict[str, Any]:
    """ModelSchemaV3: model_id + algo + parameters + output."""
    params = {}
    for f in dataclasses.fields(m.params):
        v = getattr(m.params, f.name)
        if isinstance(v, (int, float, str, bool, type(None), list)):
            params[f.name] = v
    output: Dict[str, Any] = {
        "model_category": (
            "Binomial" if m.nclasses == 2 else
            "Multinomial" if m.nclasses > 2 else "Regression"
        ),
        "training_metrics": _metrics_schema(m.training_metrics),
        "validation_metrics": _metrics_schema(m.validation_metrics),
        "cross_validation_metrics": _metrics_schema(m.cross_validation_metrics),
        "names": list(m.data_info.predictor_names),
        "domains": m.data_info.response_domain,
        "run_time": m.run_time,
    }
    for attr in ("coefficients", "exp_coef", "std_errors", "p_values", "iterations"):
        v = getattr(m, attr, None)
        if v is not None:
            output[attr] = v
    vi = getattr(m, "variable_importances", None)
    if callable(vi):
        try:
            output["variable_importances"] = vi()
        except Exception:
            pass
    return {
        "model_id": {"name": m.key},
        "algo": m.algo_name,
        "parameters": params,
        "output": output,
    }


def _coerce_params(params_cls, raw: Dict[str, Any]):
    """Form/JSON values -> typed Parameters dataclass (the schema-filling
    that api/Handler.fillFromParms does via schema metadata)."""
    fields = {f.name: f for f in dataclasses.fields(params_cls)}
    kw: Dict[str, Any] = {}
    for k, v in raw.items():
        if k not in fields:
            continue
        f = fields[k]
        ftype = f.type if isinstance(f.type, str) else getattr(f.type, "__name__", "")
        if isinstance(v, str):
            t = str(ftype)
            if "bool" in t:
                v = v.lower() in ("true", "1", "yes")
            elif "int" in t and "List" not in t:
                v = int(float(v))
            elif "float" in t:
                v = float(v)
            elif "List" in t or "list" in t:
                s = v.strip()
                if s.startswith("["):
                    v = json.loads(s.replace("'", '"'))
                else:
                    v = [x for x in s.split(",") if x]
        kw[k] = v
    try:
        return params_cls(**kw)
    except TypeError as e:
        raise RestError(400, f"bad parameters: {e}")


# ---------------------------------------------------------------------------
# registration


def register_all(r: RequestServer, server: H2OServer) -> None:
    algos = algo_map()

    # ---- cloud / ops ------------------------------------------------------
    def cloud(params):
        """CloudV3 (api/schemas3/CloudV3.java) — real members with
        heartbeat ages when an application-plane cloud is live
        (h2o3_tpu/cluster/), the single-node shape otherwise."""
        import jax

        from h2o3_tpu import cluster, native
        from h2o3_tpu.util import telemetry

        # a node that cannot reach its devices must say so (5xx), not
        # answer as a healthy node with none
        devs = jax.devices()
        local = {
            "num_cpus": os.cpu_count(),
            "devices": [str(d) for d in devs],
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            # bytes_in_use / peak_bytes_in_use / bytes_limit of the first
            # device, where the backend reports them (the CPU does not)
            "device_memory": devs[0].memory_stats(),
            "native": native.available(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        }
        out = {
            "version": __version__,
            "cloud_name": server.name,
            "cloud_size": 1,
            "cloud_healthy": True,
            "cloud_uptime_millis": int((time.time() - server.start_time) * 1000),
            "consensus": True,
            "locked": True,
            # compact process-wide totals; the full registry is /3/Metrics
            "telemetry": telemetry.REGISTRY.summary(),
            "nodes": [
                {
                    "h2o": f"127.0.0.1:{server.port}",
                    "healthy": True,
                    **local,
                }
            ],
        }
        c = cluster.local_cloud()
        if c is not None:
            nodes = c.member_schemas()
            for nd in nodes:
                if nd["name"] == c.info.name:  # only the local node can
                    nd.update(local)           # name its own devices
            out.update({
                "cloud_name": c.cloud_name,
                "node_name": c.info.name,
                "cloud_size": sum(1 for nd in nodes if not nd["client"]),
                "cloud_healthy": all(nd["healthy"] for nd in nodes),
                "consensus": c.consensus(),
                "cloud_hash": c.cloud_hash(),
                "cloud_version": c.version,
                "bad_nodes": sum(1 for nd in nodes if not nd["healthy"]),
                "nodes": nodes,
            })
        return out

    r.register("GET", "/3/Cloud", cloud, "cloud status")
    r.register("GET", "/3/Cloud/status", cloud, "cloud status (minimal)")
    r.register("GET", "/3/About", lambda p: {
        "entries": [
            {"name": "Build version", "value": __version__},
            {"name": "Backend", "value": "jax/XLA (TPU-native)"},
        ]
    }, "about")
    r.register("GET", "/3/Capabilities", lambda p: {
        "capabilities": [{"name": a} for a in sorted(algos)]
    }, "capabilities")
    r.register("GET", "/3/Metadata/endpoints", lambda p: {
        "routes": r.endpoints()
    }, "endpoint metadata")
    def shutdown(params):
        # stop the HTTP server for real (ShutdownHandler) — delayed so
        # this response still reaches the client; the hosting process
        # stays alive (it owns the TPU runtime), matching h2o.shutdown()
        # semantics of "the cluster stops answering"
        import threading as _threading

        _threading.Timer(0.3, server.stop).start()
        return {"result": "shutting down"}

    r.register("POST", "/3/Shutdown", shutdown, "stop the REST server")
    r.register("POST", "/3/GarbageCollect", lambda p: (__import__("gc").collect(), {})[1],
               "gc")

    # ---- jobs -------------------------------------------------------------
    def jobs_list(params):
        return {"jobs": [_job_schema(DKV.get(k)) for k in DKV.keys_of_type(Job)]}

    def job_get(params, job_id):
        j = DKV.get(job_id)
        if not isinstance(j, Job):
            raise RestError(404, f"job {job_id!r} not found")
        return {"jobs": [_job_schema(j)]}

    def job_cancel(params, job_id):
        j = DKV.get(job_id)
        if not isinstance(j, Job):
            raise RestError(404, f"job {job_id!r} not found")
        j.cancel()
        return {"jobs": [_job_schema(j)]}

    r.register("GET", "/3/Jobs", jobs_list, "list jobs")
    r.register("GET", "/3/Jobs/{job_id}", job_get, "job status")
    r.register("POST", "/3/Jobs/{job_id}/cancel", job_cancel, "cancel job")

    # ---- import / parse ---------------------------------------------------
    def import_files(params):
        """Path / glob / directory / URI -> raw sources (ImportFilesHandler
        + PersistManager scheme dispatch; water/persist/)."""
        from h2o3_tpu.frame.ingest import list_sources, resolve_persist

        path = params.get("path")
        if not path:
            raise RestError(400, "path required")
        try:
            sources = list_sources(path)
        except FileNotFoundError as e:
            raise RestError(404, f"path {e} not found")
        except ValueError as e:
            raise RestError(400, str(e))
        keys: List[str] = []
        fails: List[str] = []
        for src in sources:
            try:
                backend, p = resolve_persist(src)
                key = DKV.make_key("nfs:" + os.path.basename(p))
                DKV.put(key, _RawFile(p, data=backend.read_bytes(p)))
                keys.append(key)
            except Exception:
                fails.append(src)
        if not keys:
            raise RestError(
                400, f"no readable sources among {sources!r} (failed: {fails})"
            )
        return {
            "files": sources,
            "destination_frames": keys,
            "fails": fails,
            "dels": [],
        }

    def post_file(params):
        # upload_file: raw body was stashed under 'file' by the client;
        # our client sends {"data": csv_text}
        text = params.get("data")
        if text is None:
            raise RestError(400, "no file data")
        key = params.get("destination_frame") or DKV.make_key("upload")
        DKV.put(key, _RawFile("<upload>", text))
        return {"destination_frame": key, "total_bytes": len(text)}

    def _raw_of(key: str) -> _RawFile:
        v = DKV.get(key)
        if not isinstance(v, _RawFile):
            raise RestError(404, f"no raw file under {key!r}")
        return v

    def parse_setup_ep(params):
        from h2o3_tpu.frame.ingest import sniff_format

        srcs = params.get("source_frames")
        if isinstance(srcs, str):
            srcs = json.loads(srcs.replace("'", '"')) if srcs.startswith("[") else [srcs]
        raw = _raw_of(srcs[0])
        fmt = sniff_format(raw.name, raw.data)
        out = {
            "source_frames": [{"name": s} for s in srcs],
            "destination_frame": srcs[0].rsplit(":", 1)[-1] + ".hex",
            "parse_type": fmt.upper(),
        }
        if fmt == "csv":
            setup = parse_setup(raw.text)
            out.update(
                separator=ord(setup.separator),
                check_header=1 if setup.header else -1,
                column_names=setup.column_names,
                column_types=[t.name.lower() for t in setup.column_types],
                number_columns=len(setup.column_names),
            )
        return out

    def parse_ep(params):
        srcs = params.get("source_frames")
        if isinstance(srcs, str):
            srcs = json.loads(srcs.replace("'", '"')) if srcs.startswith("[") else [srcs]
        raw = _raw_of(srcs[0])
        dest = params.get("destination_frame") or DKV.make_key("parse")
        kw: Dict[str, Any] = {}
        if params.get("separator"):
            kw["separator"] = chr(int(params["separator"]))
        if params.get("check_header"):
            kw["header"] = int(params["check_header"]) == 1
        # chunk-parallel tokenization width (frame/parse.py two-phase
        # pipeline); absent -> H2O3_TPU_PARSE_WORKERS / host cores
        if params.get("parse_workers"):
            try:
                kw["workers"] = max(1, int(params["parse_workers"]))
            except (TypeError, ValueError):
                raise RestError(400, "parse_workers must be an integer")
        # forced types from ParseSetup must survive Parse (the reference's
        # two-phase parse honors the client-edited setup)
        names = params.get("column_names")
        types = params.get("column_types")
        if isinstance(names, str):
            names = json.loads(names.replace("'", '"'))
        if isinstance(types, str):
            types = json.loads(types.replace("'", '"'))
        if types:
            if not names:
                names = parse_setup(raw.text).column_names
            kw["column_types"] = {
                n: t for n, t in zip(names, types) if t
            }
        job = Job(f"parse {dest}").start()
        try:
            from h2o3_tpu.frame.ingest import parse_bytes, rbind_all

            # multi-file: parse each + rbind (ParseDataset parseAllKeys)
            fr = rbind_all(
                [
                    parse_bytes(_raw_of(s).raw_name, _raw_of(s).raw_data, **kw)
                    for s in srcs
                ]
            )
            DKV.put(dest, fr)
            job.dest = dest
            job.done()
        except Exception as e:
            job.fail(e)
            raise RestError(400, f"parse failed: {e}")
        return {"job": _job_schema(job), "destination_frame": {"name": dest}}

    def import_sql(params):
        """/3/ImportSQLTable (water/jdbc/SQLManager.java; sqlite here)."""
        from h2o3_tpu.frame.ingest import import_sql_table

        url = params.get("connection_url")
        if not url:
            raise RestError(400, "connection_url required")
        cols = params.get("columns")
        if isinstance(cols, str) and cols:
            cols = [c for c in cols.split(",") if c]
        try:
            fr = import_sql_table(
                url,
                table=params.get("table"),
                select_query=params.get("select_query"),
                columns=cols or None,
                partition_column=params.get("partition_column"),
                num_partitions=int(params.get("num_partitions") or 1),
            )
        except FileNotFoundError as e:
            raise RestError(404, f"database not found: {e}")
        except ValueError as e:
            raise RestError(400, str(e))
        dest = params.get("destination_frame") or DKV.make_key("sql")
        fr.key = dest
        DKV.put(dest, fr)
        return {"destination_frame": {"name": dest},
                "rows": fr.nrows, "cols": fr.ncols}

    r.register("POST", "/3/ImportSQLTable", import_sql, "import a SQL table")
    r.register("POST", "/3/ImportFiles", import_files, "import a file")
    r.register("POST", "/3/PostFile", post_file, "upload a file body")
    r.register("POST", "/3/ParseSetup", parse_setup_ep, "guess parse setup")
    r.register("POST", "/3/Parse", parse_ep, "parse to frame")

    # ---- frames -----------------------------------------------------------
    def _chunk_homes(v):
        """Chunk layout + replica health for a ring-homed frame; None
        for an ordinary node-local frame (the common case: one getattr)."""
        if getattr(v, "chunk_layout", None) is None:
            return None
        from h2o3_tpu.cluster.frames import layout_health

        return layout_health(v)

    def frames_list(params):
        out = []
        for k in DKV.keys_of_type(Frame):
            # peek: listing a spilled frame must not fault it back in
            v = DKV.peek(k)
            if v is None:
                continue
            row = {"frame_id": {"name": k}, "rows": v.nrows,
                   "num_columns": v.ncols}
            homes = _chunk_homes(v)
            if homes is not None:
                row["chunk_homes"] = homes
            out.append(row)
        return {"frames": out}

    def frame_get(params, frame_id):
        rows = int(params.get("row_count", 10))
        fr = _get_frame(frame_id)
        schema = _frame_schema(fr, frame_id, rows)
        homes = _chunk_homes(fr)
        if homes is not None:
            schema["chunk_homes"] = homes
        return {"frames": [schema]}

    def frame_summary(params, frame_id):
        return frame_get(params, frame_id)

    def frame_columns(params, frame_id):
        fr = _get_frame(frame_id)
        return {"columns": _frame_schema(fr, frame_id)["columns"]}

    def frame_delete(params, frame_id):
        _get_frame(frame_id)
        try:
            DKV.remove(frame_id)
        except ValueError as e:  # Lockable: in use by a running job
            raise RestError(409, str(e))
        return {"frame_id": {"name": frame_id}}

    def frames_delete_all(params):
        skipped = []
        for k in DKV.keys_of_type(Frame):
            try:
                DKV.remove(k)
            except ValueError:  # locked by a running job: skip, not fail
                skipped.append(k)
        return {"skipped_locked": skipped}

    def download_dataset(params):
        """CSV straight from the columns — no pandas: the pandas/pyarrow
        string-index path is not thread-safe under ThreadingHTTPServer and
        segfaulted the server in testing."""
        import csv as _csv

        fr = _get_frame(params.get("frame_id", ""))
        buf = io.StringIO()
        w = _csv.writer(buf, lineterminator="\n")
        w.writerow(fr.names)
        rendered = []
        for c in fr.columns:
            if c.type is ColType.CAT:
                dom = c.domain
                rendered.append(
                    [dom[v] if v >= 0 else "" for v in c.data]
                )
            elif c.type is ColType.STR:
                rendered.append(["" if v is None else str(v) for v in c.data])
            else:
                rendered.append([
                    "" if np.isnan(v) else (repr(int(v)) if float(v).is_integer() else repr(float(v)))
                    for v in c.data
                ])
        for row in zip(*rendered):
            w.writerow(row)
        return buf.getvalue().encode()

    def split_frame(params):
        fr = _get_frame(params.get("dataset", params.get("frame_id", "")))
        ratios = params.get("ratios", "[0.75]")
        if isinstance(ratios, str):
            ratios = json.loads(ratios)
        ratios = [float(x) for x in np.atleast_1d(ratios)]
        seed = int(params.get("seed", -1))
        rng = np.random.default_rng(None if seed == -1 else seed)
        u = rng.random(fr.nrows)
        bounds = np.cumsum(ratios)
        dests = params.get("destination_frames")
        if isinstance(dests, str):
            dests = json.loads(dests.replace("'", '"'))
        keys = []
        lo = 0.0
        all_bounds = list(bounds)
        if not all_bounds or all_bounds[-1] < 1.0 - 1e-12:
            all_bounds.append(1.0)  # remainder split only if ratios < 1
        for i, hi in enumerate(all_bounds):
            mask = (u >= lo) & (u < hi)
            lo = hi
            sub = fr.rows(mask)
            key = (dests[i] if dests and i < len(dests)
                   else DKV.make_key("split"))
            DKV.put(key, sub)
            keys.append(key)
        return {"destination_frames": [{"name": k} for k in keys]}

    r.register("GET", "/3/Frames", frames_list, "list frames")
    r.register("GET", "/3/Frames/{frame_id}", frame_get, "frame + preview")
    r.register("GET", "/3/Frames/{frame_id}/summary", frame_summary, "frame summary")
    r.register("GET", "/3/Frames/{frame_id}/columns", frame_columns, "frame columns")
    r.register("DELETE", "/3/Frames/{frame_id}", frame_delete, "delete frame")
    r.register("DELETE", "/3/Frames", frames_delete_all, "delete all frames")
    r.register("GET", "/3/DownloadDataset", download_dataset, "frame as csv")
    r.register("POST", "/3/SplitFrame", split_frame, "split a frame")

    # ---- rapids / sessions ------------------------------------------------
    def new_session(params):
        s = Session()
        _SESSIONS[s.id] = s
        return {"session_key": s.id}

    def end_session(params, session_id):
        s = _SESSIONS.pop(session_id, None)
        n = s.end() if s else 0
        return {"session_key": session_id, "frames_removed": n}

    def rapids_exec_ep(params):
        ast = params.get("ast")
        if not ast:
            raise RestError(400, "ast required")
        sid = params.get("session_id")
        session = _SESSIONS.get(sid) if sid else None
        if sid and session is None:
            session = _SESSIONS[sid] = Session(sid)
        try:
            val = exec_rapids(ast, session=session)
        except Exception as e:
            raise RestError(400, f"rapids error: {e}")
        # RapidsSchemaV3 family: scalar / string / frame
        if val.is_frame():
            fr = val.as_frame()
            key = getattr(fr, "key", None) or DKV.make_key("rapids")
            DKV.put(key, fr)
            out = {
                "key": {"name": key},
                "num_rows": fr.nrows,
                "num_cols": fr.ncols,
            }
            # a chunk-homed result stays on the ring: report the layout
            # (shape answers come off it — nothing here gathers chunks)
            lay = getattr(fr, "chunk_layout", None)
            if lay is not None:
                out["chunk_homed"] = True
                out["chunk_groups"] = len(lay["groups"])
            return out
        if val.is_num():
            return {"scalar": val.as_num()}
        if val.is_str():
            return {"string": val.as_str()}
        try:
            return {"scalar": val.as_nums().tolist()}
        except Exception:
            return {"string": repr(val)}

    r.register("POST", "/4/sessions", new_session, "new rapids session")
    r.register("DELETE", "/4/sessions/{session_id}", end_session, "end session")
    r.register("POST", "/99/Rapids", rapids_exec_ep, "execute a rapids ast")

    def flow_replay(params, name):
        """Load a notebook document saved under NPS category "notebook"
        (the reference Flow's own save location, NodePersistentStorage)
        and execute its cells in order server-side — the h2o-web flow
        replay, minus the browser."""
        import json as _json

        from h2o3_tpu.util import nps

        try:
            raw = nps.get("notebook", name)
        except FileNotFoundError:
            raise RestError(404, f"no saved flow {name!r}")
        try:
            doc = _json.loads(raw.decode())
        except Exception:
            raise RestError(400, f"flow {name!r} is not a JSON document")
        out = []
        for cell in doc.get("cells", []):
            ast = cell.get("input") if isinstance(cell, dict) else None
            if not ast:
                continue
            try:
                res = rapids_exec_ep(
                    {"ast": ast, "session_id": params.get("session_id")})
                out.append({"input": ast, "ok": True, "result": res})
            except RestError as e:
                out.append({"input": ast, "ok": False, "error": str(e)})
        return {"name": name, "cells": out}

    r.register("POST", "/99/Flow/{name}/run", flow_replay,
               "replay a saved flow document")

    # ---- model builders ---------------------------------------------------
    def builders_list(params):
        return {
            "model_builders": {
                a: {"algo": a, "visibility": "Stable"} for a in sorted(algos)
            }
        }

    def _default_of(f: dataclasses.Field):
        if f.default is not dataclasses.MISSING and isinstance(
            f.default, (int, float, str, bool, type(None))
        ):
            return f.default
        return None  # default_factory or non-scalar default

    def builder_get(params, algo):
        if algo not in algos:
            raise RestError(404, f"unknown algo {algo!r}")
        _, pcls = algos[algo]
        return {
            "model_builders": {
                algo: {
                    "algo": algo,
                    "parameters": [
                        {"name": f.name, "default_value": _default_of(f)}
                        for f in dataclasses.fields(pcls)
                    ],
                }
            }
        }

    #: request fields consumed by the route itself, not the algo params
    _TRAIN_EXTRA = frozenset({"training_frame", "validation_frame", "model_id"})

    def train(params, algo):
        if algo not in algos:
            raise RestError(404, f"unknown algo {algo!r}")
        bcls, pcls = algos[algo]
        # an unknown param must 400, not silently drop (the REST face of
        # the no-silent-param guard; reference: ModelBuilderHandler rejects
        # unknown schema fields)
        unknown = set(params) - {f.name for f in dataclasses.fields(pcls)} - _TRAIN_EXTRA
        if unknown:
            raise RestError(
                400, f"unknown parameters for {algo}: {sorted(unknown)}"
            )
        # generic "trains" from an artifact, not a frame (hex/generic)
        fr = (
            _get_frame(params.get("training_frame", ""))
            if algo != "generic"
            else None
        )
        valid = (
            _get_frame(params["validation_frame"])
            if params.get("validation_frame")
            else None
        )
        p = _coerce_params(pcls, params)
        builder = bcls(p)
        try:
            model = builder.train(fr, valid)
        except RestError:
            raise
        except Exception as e:
            raise RestError(400, f"{algo} train failed: {type(e).__name__}: {e}")
        if params.get("model_id"):
            DKV.rekey(model, params["model_id"])
        job = builder.job  # ModelBuilder.train always creates one
        if job is None:  # defensive: synthesize a finished job
            job = Job(f"{algo} train").start()
            job.done()
        job.dest = model.key
        return {"job": _job_schema(job), "model_id": {"name": model.key}}

    r.register("GET", "/3/ModelBuilders", builders_list, "list algos")
    r.register("GET", "/3/ModelBuilders/{algo}", builder_get, "algo parameters")
    r.register("POST", "/3/ModelBuilders/{algo}", train, "train a model")

    # ---- models -----------------------------------------------------------
    def models_list(params):
        out = []
        for k in DKV.keys_of_type(Model):
            out.append({"model_id": {"name": k}, "algo": DKV.get(k).algo_name})
        return {"models": out}

    def model_get(params, model_id):
        return {"models": [_model_schema(_get_model(model_id))]}

    def model_delete(params, model_id):
        _get_model(model_id)
        DKV.remove(model_id)
        return {}

    def models_delete_all(params):
        for k in DKV.keys_of_type(Model):
            DKV.remove(k)
        return {}

    def model_mojo(params, model_id):
        m = _get_model(model_id)
        with tempfile.NamedTemporaryFile(suffix=".mojo", delete=False) as f:
            path = f.name
        try:
            fmt = str(params.get("format", "")).strip().lower()
            if fmt == "reference":
                # the actual H2O-3 MOJO zip layout (models/mojo_ref.py)
                from h2o3_tpu.models.mojo_ref import write_mojo as _write_ref

                try:
                    _write_ref(m, path)
                except ValueError as e:
                    raise RestError(400, str(e))
            elif fmt in ("", "native"):
                m.download_mojo(path)
            else:
                # an explicit unknown format must not silently fall back:
                # the client would feed the wrong artifact downstream
                raise RestError(400, f"unknown mojo format {fmt!r} "
                                     f"(use 'native' or 'reference')")
            with open(path, "rb") as f:
                return f.read()
        finally:
            os.unlink(path)

    def mojo_pipeline(params):
        """Compose trained models into ONE reference-layout pipeline MOJO
        (hex/genmodel/MojoPipelineWriter — h2o.make_mojo_pipeline's
        role): body {models: {alias: model_id}, input_mapping:
        {generated_col: "alias:pred_idx"}, main_model: alias}; returns
        the zip bytes."""
        from h2o3_tpu.models.mojo_ref import write_pipeline_mojo

        models_spec = params.get("models")
        if isinstance(models_spec, str):
            models_spec = json.loads(models_spec)
        mapping = params.get("input_mapping") or {}
        if isinstance(mapping, str):
            mapping = json.loads(mapping)
        main = params.get("main_model")
        if not models_spec or not main:
            raise RestError(400, "models (alias->model_id) and main_model "
                                 "are required")
        models = {alias: _get_model(mid)
                  for alias, mid in models_spec.items()}
        with tempfile.NamedTemporaryFile(suffix=".zip",
                                         delete=False) as f:
            path = f.name
        try:
            try:
                write_pipeline_mojo(models, mapping, main, path)
            except ValueError as e:
                raise RestError(400, str(e))
            with open(path, "rb") as f:
                return f.read()
        finally:
            os.unlink(path)

    def _predict_out(m, model_id, frame_id, params, pred, metrics_fn):
        """Assemble one /3/Predictions response: register the predictions
        frame, best-effort metrics + the DKV scoring record."""
        dest = params.get("predictions_frame") or DKV.make_key("pred")
        DKV.put(dest, pred)
        out: Dict[str, Any] = {
            "model_metrics": [
                {
                    "frame": {"name": frame_id},
                    "model": {"name": model_id},
                    "predictions_frame": {"name": dest},
                }
            ]
        }
        try:
            mm = metrics_fn()
            out["model_metrics"][0].update(_metrics_schema(mm) or {})
            # leave the DKV-resident scoring record the /3/ModelMetrics
            # routes fetch/delete (hex/ModelMetrics.buildKey)
            from h2o3_tpu.api.handlers_ops import record_scoring

            record_scoring(m, frame_id, mm)
        except Exception:
            pass  # frames without a response can still be scored
        return out

    def predict_batch(requests):
        """Batched /3/Predictions body: the serving coalescer keys batches
        on model_id, so every entry here shares one model and the whole
        batch costs ONE raw-score dispatch (Model.predict_raw_batched) —
        identical frames score once and share the result, distinct frames
        row-stack.  Returns one result-or-exception per entry, aligned;
        exceptions map to the same status the serial handler produces."""
        results: List[Any] = [None] * len(requests)
        try:
            m = _get_model(requests[0][1]["model_id"])
        except BaseException as e:  # noqa: BLE001
            if isinstance(e, RestError) and e.status == 404:
                # not local: a multi-node cloud can still serve it — the
                # serving ring forwards the whole batch to the model's
                # home (or its replicas), cluster/serving.py
                from h2o3_tpu.cluster import serving as _serving

                try:
                    fwd = _serving.forward_predict(
                        requests, requests[0][1]["model_id"])
                except BaseException as fe:  # noqa: BLE001
                    return [fe] * len(requests)
                if fwd is not None:
                    return fwd
            return [e] * len(requests)
        # models with a bespoke predict()/score shape (PCA names PC
        # columns, aggregator has no row scoring) can't share a raw pass:
        # serial per entry, exactly the pre-coalescer behavior
        if type(m).predict is not Model.predict:
            for i, (params, kw) in enumerate(requests):
                try:
                    fr = _get_frame(kw["frame_id"])
                    results[i] = _predict_out(
                        m, kw["model_id"], kw["frame_id"], params,
                        m.predict(fr), lambda fr=fr: m.model_performance(fr))
                except BaseException as e:  # noqa: BLE001
                    results[i] = e
            return results
        frames: List[Any] = [None] * len(requests)
        for i, (_params, kw) in enumerate(requests):
            try:
                frames[i] = _get_frame(kw["frame_id"])
            except BaseException as e:  # noqa: BLE001
                results[i] = e
        live = [i for i in range(len(requests)) if results[i] is None]
        try:
            scored: List[Any] = m.predict_raw_batched(
                [frames[i] for i in live])
        except BaseException:  # noqa: BLE001
            # one bad frame must not poison the batch: retry serially so
            # only the offender fails
            scored = []
            for i in live:
                try:
                    pre = m._apply_preprocessors(frames[i])
                    scored.append((m._predict_raw(pre), pre))
                except BaseException as e:  # noqa: BLE001
                    scored.append(e)
        own_perf = type(m).model_performance is Model.model_performance
        for i, s in zip(live, scored):
            params, kw = requests[i]
            if isinstance(s, BaseException):
                results[i] = s
                continue
            try:
                raw, pre = s
                results[i] = _predict_out(
                    m, kw["model_id"], kw["frame_id"], params,
                    m.prediction_from_raw(raw),
                    # reuse the batch's raw scores for the metrics instead
                    # of scoring again (unless the model overrides
                    # model_performance with stored stats of its own)
                    (lambda raw=raw, pre=pre: m._metrics_from_raw(pre, raw))
                    if own_perf
                    else (lambda fr=frames[i]: m.model_performance(fr)))
            except BaseException as e:  # noqa: BLE001
                results[i] = e
        return results

    def predict(params, model_id, frame_id):
        # a single request IS a batch of one — serial and coalesced
        # scoring share every line of code, which is what makes the
        # batched results bit-identical by construction
        out = predict_batch(
            [(params, {"model_id": model_id, "frame_id": frame_id})])[0]
        if isinstance(out, BaseException):
            raise out
        return out

    def _predict_rows_hint(kw):
        fr = DKV.peek(kw.get("frame_id", ""))
        try:
            return int(getattr(fr, "nrows", 0) or 0)
        except Exception:
            return 0

    # coalescing contract with the event-loop server: batch same-model
    # requests (key), bound batches by summed rows over distinct frames
    # (group/rows)
    predict._h2o3_batch = predict_batch
    predict._h2o3_batch_key = lambda kw: kw.get("model_id")
    predict._h2o3_batch_group = lambda kw: kw.get("frame_id")
    predict._h2o3_batch_rows = _predict_rows_hint

    # ---- binary persistence (Model.exportBinaryModel / importBinaryModel,
    # /3/Models/.../save + /99/Models.bin; FramePersist save/load) ----------
    def _server_path(params, default_name: str) -> str:
        """'dir' is a target DIRECTORY (the h2o-py save_model contract) —
        created if missing — unless it names a file explicitly via a known
        artifact extension."""
        d = params.get("dir")
        if not d:
            raise RestError(400, "missing 'dir' (server-side target path)")
        d = os.path.expanduser(d)
        if os.path.splitext(d)[1] in (".bin", ".h2f", ".mojo", ".zip"):
            os.makedirs(os.path.dirname(d) or ".", exist_ok=True)
            return d
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, default_name)

    def model_save(params, model_id):
        from h2o3_tpu.models.persist import save_model as _save_model

        m = _get_model(model_id)
        path = _server_path(params, f"{model_id}.bin")
        force = str(params.get("force", "true")).lower() in ("true", "1", "yes")
        if os.path.exists(path) and not force:
            raise RestError(409, f"{path} exists and force is false")
        return {"dir": _save_model(m, path)}

    def model_load(params):
        from h2o3_tpu.models.persist import load_model as _load_model

        d = params.get("dir")
        if not d:
            raise RestError(400, "missing 'dir' (server-side model file)")
        try:
            # decode without touching the DKV so a non-model file (e.g. a
            # grid export) can be rejected with no side effects
            m = _load_model(os.path.expanduser(d), register=False)
        except FileNotFoundError:
            raise RestError(404, f"no model file at {d!r}")
        except Exception as e:
            raise RestError(400, f"model load failed: {type(e).__name__}: {e}")
        if not isinstance(m, Model):
            raise RestError(400, f"{d!r} is not a model export")
        if params.get("model_id"):
            # new key only — the file's saved key stays untouched so a live
            # model sharing it is never clobbered
            m.key = params["model_id"]
        DKV.put(m.key, m)
        # an imported model joins the serving ring exactly like a trained
        # one: on a multi-node cloud its blob homes (+ replicates) so ANY
        # member's /3/Predictions can reach it (cluster/serving.py)
        from h2o3_tpu.cluster import serving as _serving

        _serving.home_model(m)
        return {"models": [{"model_id": {"name": m.key}, "algo": m.algo_name}]}

    def frame_save(params, frame_id):
        from h2o3_tpu.frame.persist import save_frame as _save_frame

        fr = _get_frame(frame_id)
        path = _server_path(params, f"{frame_id}.h2f")
        return {"dir": _save_frame(fr, path)}

    def frame_load(params):
        from h2o3_tpu.frame.persist import load_frame as _load_frame

        d = params.get("dir")
        if not d:
            raise RestError(400, "missing 'dir' (server-side frame file)")
        try:
            fr = _load_frame(os.path.expanduser(d))
        except FileNotFoundError:
            raise RestError(404, f"no frame file at {d!r}")
        key = params.get("frame_id") or fr.key or DKV.make_key("frame")
        fr.key = key
        DKV.put(key, fr)
        return {"frames": [{"frame_id": {"name": key}, "rows": fr.nrows,
                            "num_columns": fr.ncols}]}

    def mojo_import(params):
        """Import a MOJO archive as a servable Generic model (hex/generic)."""
        from h2o3_tpu.models.generic import import_mojo as _import_mojo

        path = params.get("dir") or params.get("path")
        if not path:
            raise RestError(400, "missing 'dir' (server-side mojo path)")
        try:
            m = _import_mojo(os.path.expanduser(path), params.get("model_id"))
        except FileNotFoundError:
            raise RestError(404, f"no mojo at {path!r}")
        except Exception as e:
            raise RestError(400, f"mojo import failed: {type(e).__name__}: {e}")
        return {"models": [{"model_id": {"name": m.key}, "algo": m.algo_name,
                            "source_algo": m.source_algo}]}

    r.register("GET", "/3/Models", models_list, "list models")
    r.register("GET", "/3/Models/{model_id}", model_get, "model details")
    r.register("DELETE", "/3/Models/{model_id}", model_delete, "delete model")
    r.register("DELETE", "/3/Models", models_delete_all, "delete all models")
    r.register("GET", "/3/Models/{model_id}/mojo", model_mojo, "download mojo")
    r.register("POST", "/3/Models/{model_id}/save", model_save,
               "save model binary server-side")
    r.register("POST", "/99/Models.bin", model_load, "load model binary")
    r.register("POST", "/3/Frames/{frame_id}/save", frame_save,
               "save frame server-side")
    r.register("POST", "/3/Frames/load", frame_load, "load a saved frame")
    r.register("POST", "/99/MojoPipeline", mojo_pipeline,
               "compose models into a reference pipeline MOJO")
    r.register("POST", "/99/Models.mojo", mojo_import,
               "import a MOJO as a Generic model")
    r.register(
        "POST", "/3/Predictions/models/{model_id}/frames/{frame_id}", predict,
        "score a frame",
    )

    # ---- grids ------------------------------------------------------------
    def grid_train(params, algo):
        if algo not in algos:
            raise RestError(404, f"unknown algo {algo!r}")
        bcls, pcls = algos[algo]
        fr = _get_frame(params.get("training_frame", ""))
        hyper = params.get("hyper_parameters")
        if isinstance(hyper, str):
            hyper = json.loads(hyper)
        if not isinstance(hyper, dict) or not hyper:
            raise RestError(400, "hyper_parameters (dict) required")
        crit_raw = params.get("search_criteria") or {}
        if isinstance(crit_raw, str):
            crit_raw = json.loads(crit_raw)
        crit = SearchCriteria(**{
            k: v for k, v in crit_raw.items()
            if k in {f.name for f in dataclasses.fields(SearchCriteria)}
        })
        base = _coerce_params(pcls, params)
        gs = GridSearch(bcls, base, hyper, crit)
        # the search runs under a Job so /3/Jobs shows live cluster-wide
        # completion while members stream search_progress events into it
        job = Job(f"grid search ({algo})").start()
        try:
            grid = gs.train(fr, job=job)
        except Exception as e:
            job.fail(e)
            raise
        job.dest = grid.grid_id
        job.done()
        want = params.get("grid_id")
        if want and want != grid.grid_id:
            # client-chosen grid id (GridSearchHandler honors grid_id)
            old = grid.grid_id
            grid.grid_id = want
            DKV.put(want, grid)
            if old in DKV:
                DKV.remove(old)
        return {
            "grid_id": {"name": grid.grid_id},
            "model_ids": [{"name": k} for k in grid.model_ids],
            "failure_details": [msg for _, msg in grid.failures],
            "job": _job_schema(job),
        }

    def grids_list(params):
        out = []
        for k in DKV.keys_of_type(Grid):
            out.append({"grid_id": {"name": k}, "model_count": len(DKV.get(k).models)})
        return {"grids": out}

    def grid_get(params, grid_id):
        g = DKV.get(grid_id)
        if not isinstance(g, Grid):
            raise RestError(404, f"grid {grid_id!r} not found")
        sort_by = params.get("sort_by", "auto")
        gs = g.get_grid(sort_by)
        out = {
            "grid_id": {"name": grid_id},
            "model_ids": [{"name": k} for k in gs.model_ids],
            "hyper_params": gs.hyper_params,
            "failure_details": [msg for _, msg in gs.failures],
        }
        # live cluster-wide completion while a distributed search runs
        # (members stream per-model search_progress events to the caller)
        try:
            from h2o3_tpu.cluster.search import search_progress

            prog = search_progress(grid_id)
        except Exception:
            prog = None
        if prog is not None:
            out["progress"] = prog
        return out

    def grid_export(params, grid_id):
        """export_grid (hex/grid Grid.exportBinary): pickle-free archive."""
        g = DKV.get(grid_id)
        if not isinstance(g, Grid):
            raise RestError(404, f"grid {grid_id!r} not found")
        path = _server_path(params, f"{grid_id}.bin")
        return {"dir": g.save(path)}

    def grid_import(params):
        d = params.get("dir")
        if not d:
            raise RestError(400, "missing 'dir' (server-side grid file)")
        try:
            g = Grid.load(os.path.expanduser(d))
        except FileNotFoundError:
            raise RestError(404, f"no grid file at {d!r}")
        except Exception as e:
            raise RestError(400, f"grid import failed: {type(e).__name__}: {e}")
        return {"grid_id": {"name": g.grid_id}, "model_ids": g.model_ids}

    def recovery_resume(params):
        """/3/Recovery/resume (hex/faulttolerance autoRecover): resume an
        interrupted Recoverable from its auto-recovery directory."""
        from h2o3_tpu.recovery import Recovery, auto_recover

        d = params.get("dir") or params.get("recovery_dir")
        if not d:
            raise RestError(400, "missing 'dir' (auto-recovery directory)")
        if not Recovery.present(d):
            raise RestError(404, f"no recovery snapshot in {d!r}")
        try:
            result = auto_recover(d)
        except Exception as e:
            raise RestError(400, f"recovery failed: {type(e).__name__}: {e}")
        out: Dict[str, Any] = {"resumed": True}
        if isinstance(result, Grid):
            out["grid_id"] = {"name": result.grid_id}
            out["model_ids"] = result.model_ids
        return out

    def udf_upload(params):
        """/3/CustomMetric upload (water/udf CFuncRef; gated by
        H2O3_TPU_ENABLE_UDF=1 — uploaded code is code execution)."""
        from h2o3_tpu import udf

        name = params.get("name")
        source = params.get("source")
        if not name or not source:
            raise RestError(400, "name and source required")
        try:
            udf.compile_metric(name, source)
        except PermissionError as e:
            raise RestError(403, str(e))
        except Exception as e:
            raise RestError(400, f"bad UDF: {type(e).__name__}: {e}")
        return {"name": name}

    def udf_eval(params):
        """Evaluate a registered custom metric on (model, frame)."""
        from h2o3_tpu import udf

        m = _get_model(params.get("model_id", ""))
        fr = _get_frame(params.get("frame_id", ""))
        name = params.get("name")
        if not name:
            raise RestError(400, "name required")
        try:
            fn = udf.get_metric(name)
        except KeyError as e:
            raise RestError(404, str(e))
        try:
            value = udf.custom_metric(m, fr, fn)
        except Exception as e:  # data errors are the caller's 400, not 404
            raise RestError(
                400, f"metric evaluation failed: {type(e).__name__}: {e}"
            )
        return {"name": name, "value": value}

    r.register("POST", "/3/CustomMetric", udf_upload, "upload a metric UDF")
    r.register("POST", "/3/CustomMetric/eval", udf_eval, "evaluate a metric UDF")
    r.register("POST", "/3/Recovery/resume", recovery_resume,
               "resume from auto-recovery snapshot")
    r.register("POST", "/99/Grid/{algo}", grid_train, "grid search")
    r.register("GET", "/99/Grids", grids_list, "list grids")
    r.register("GET", "/99/Grids/{grid_id}", grid_get, "grid details")
    r.register("POST", "/99/Grids/{grid_id}/export", grid_export, "export grid")
    r.register("POST", "/99/Grids/import", grid_import, "import grid")

    # ---- automl (h2o-automl REST: /99/AutoMLBuilder, leaderboard) ---------
    def automl_build(params):
        from h2o3_tpu.automl import AutoML

        fr = _get_frame(params.get("training_frame", ""))
        y = params.get("response_column")
        if not y:
            raise RestError(400, "response_column required")
        kw: Dict[str, Any] = {}
        for k, cast in (
            ("max_models", int), ("max_runtime_secs", float), ("seed", int),
            ("nfolds", int), ("sort_metric", str),
        ):
            if params.get(k) is not None:
                kw[k] = cast(params[k])
        for k in ("include_algos", "exclude_algos"):
            v = params.get(k)
            if isinstance(v, str):
                v = json.loads(v.replace("'", '"'))
            if v:
                kw[k] = v
        aml = AutoML(**kw)
        x = params.get("x")
        if isinstance(x, str):
            x = json.loads(x.replace("'", '"'))
        try:
            aml.train(y=y, training_frame=fr, x=x)
        except Exception as e:
            raise RestError(400, f"automl failed: {type(e).__name__}: {e}")
        return {
            "automl_id": {"name": aml.project_key},
            "leader": {"name": aml.leader.key},
            "leaderboard": aml.leaderboard.as_table(),
        }

    def automl_get(params, automl_id):
        from h2o3_tpu.automl import AutoML

        aml = DKV.get(automl_id)
        if not isinstance(aml, AutoML):
            raise RestError(404, f"automl {automl_id!r} not found")
        return {
            "automl_id": {"name": aml.project_key},
            "leader": {"name": aml.leader.key} if aml.leader else None,
            "leaderboard": aml.leaderboard.as_table(),
            "event_log": aml.event_log.events,
        }

    r.register("POST", "/99/AutoMLBuilder", automl_build, "run automl")
    r.register("GET", "/99/AutoML/{automl_id}", automl_get, "automl results")

    # ---- diagnostics (TimeLine / logs / jstack analogues) -----------------
    # ---- observability (water/TimeLine.java, util/Log.java, JStack) -------
    def _truthy(v) -> bool:
        return str(v).lower() in ("1", "true", "yes")

    def _active_cloud():
        from h2o3_tpu import cluster

        return cluster.active_cloud()

    def timeline_ep(params):
        """Real event ring: compiles, training blocks, REST requests
        (water/TimeLine.java:22,75 snapshot semantics).  With
        ``?cluster=true`` on a multi-node cloud: every member's ring is
        collected over RPC, each remote event is tagged ``node=`` and its
        wall clock shifted by the heartbeat-derived skew estimate, and the
        merged stream comes back sorted — the reference's cluster-snapshot
        TimeLine (init/TimelineSnapshot.java), minus the UDP packet log."""
        from h2o3_tpu.util import timeline

        # `count` is the documented name; `n` is the short alias thin
        # clients use (both untested before the telemetry PR)
        n = int(params.get("count", params.get("n", 1000)))
        cloud = _active_cloud() if _truthy(params.get("cluster")) else None
        if cloud is None:
            return _attach_ledgers({
                "events": timeline.snapshot(n),
                "total_events": timeline.total_events(),
                "now": int(time.time() * 1000),
            }, params)
        results, errors = cloud.poll_members(
            "timeline_snapshot", {"count": n})
        members = {m.info.name: m for m in cloud.members_sorted()}
        events = []
        nodes_meta = []
        for name in sorted(results):
            snap = results[name] or {}
            m = members.get(name)
            is_self = name == cloud.info.name
            skew_ms = 0.0
            if not is_self and m is not None and m.clock_skew_ms is not None:
                skew_ms = float(m.clock_skew_ms)
            for ev in snap.get("events", []):
                ev = dict(ev)
                ev.setdefault("node", name)
                # a remote clock ahead of ours by skew_ms reads skew_ms
                # too late: shift its events back onto our clock
                ev["ns"] = int(ev.get("ns", 0) - skew_ms * 1e6)
                events.append(ev)
            nodes_meta.append({
                "name": name,
                "skew_ms": round(skew_ms, 3),
                "rtt_ms": (None if is_self or m is None or m.rtt_ms is None
                           else round(m.rtt_ms, 3)),
                "events": len(snap.get("events", [])),
                "total_events": snap.get("total_events", 0),
            })
        for name in sorted(errors):
            nodes_meta.append({"name": name, "error": errors[name]})
        events.sort(key=lambda e: e.get("ns", 0))
        return _attach_ledgers({
            "events": events,
            "nodes": nodes_meta,
            "partial": bool(errors),
            "total_events": sum(nm.get("total_events", 0)
                                for nm in nodes_meta),
            "now": int(time.time() * 1000),
        }, params)

    def _attach_ledgers(resp, params):
        """``?ledgers=true``: attach this node's cost-ledger entries for
        every trace id present in the returned events, so a saved
        timeline snapshot carries the data trace_view.py needs to render
        per-span cost columns."""
        if not _truthy(params.get("ledgers")):
            return resp
        from h2o3_tpu.util import ledger as ledger_mod

        tids = [e.get("trace_id") for e in resp.get("events", [])
                if e.get("trace_id")]
        resp["ledgers"] = ledger_mod.LEDGER.snapshot_many(tids)
        return resp

    def traces_ep(params, trace_id):
        """Per-trace cost breakdown (node x category), federated: every
        member is asked for its ledger entry over the trace_ledger RPC
        and the per-node maps merge — 404 only when NO reachable member
        knows the trace; an unreachable member degrades the answer to
        ``partial: true``, never a 5xx."""
        from h2o3_tpu.util import ledger as ledger_mod

        cloud = _active_cloud()
        if cloud is None:
            entry = ledger_mod.LEDGER.get(trace_id)
            if entry is None:
                raise RestError(
                    404, f"no cost ledger for trace {trace_id!r}")
            entry["partial"] = False
            return entry
        results, errors = cloud.poll_members(
            "trace_ledger", {"trace_id": trace_id})
        nodes: Dict[str, Any] = {}
        spans: Dict[str, Any] = {}
        meta: Dict[str, Any] = {}
        known = False
        for name in sorted(results):
            led = (results[name] or {}).get("ledger")
            if not led:
                continue
            known = True
            # merge by OVERWRITING per-node keys, never summing: each
            # node's charges live under its own name (disjoint in a real
            # multi-process cloud), and in-process test clouds share one
            # process-wide ledger — every member returns the same entry,
            # so summing would multiply every cost by the member count
            for node, cats in (led.get("nodes") or {}).items():
                nodes[node] = dict(cats)
            for sid, cats in (led.get("spans") or {}).items():
                spans[sid] = dict(cats)
            for k, v in led.items():
                if k not in ("trace_id", "nodes", "spans", "total"):
                    meta.setdefault(k, v)
        if not known:
            raise RestError(404, f"no cost ledger for trace {trace_id!r}")
        total: Dict[str, float] = {}
        for cats in nodes.values():
            for k, v in cats.items():
                total[k] = total.get(k, 0.0) + v
        out = {"trace_id": trace_id, "nodes": nodes, "spans": spans,
               "total": total, "partial": bool(errors)}
        if errors:
            out["errors"] = {k: errors[k] for k in sorted(errors)}
        for k, v in meta.items():
            out.setdefault(k, v)
        return out

    def slowops_ep(params):
        """The slow-op exemplar log: the N worst traces per route above
        the threshold, each with its ledger snapshot attached.
        ``?route=`` filters to one route.  The serving node's watchdog
        summary rides along so one scrape answers "slow AND sick?"."""
        from h2o3_tpu.cluster import health as health_mod
        from h2o3_tpu.util import ledger as ledger_mod

        out = ledger_mod.SLOWOPS.snapshot(route=params.get("route") or None)
        out["health"] = health_mod.summary()
        return out

    def diagnostics_ep(params):
        """One-call support bundle: identity + knobs, watchdog verdicts,
        the last-K flight events, worst SlowOps, membership view and
        thread stacks.  ``?cluster=true`` federates over the
        diagnostics_snapshot RPC — an unreachable member degrades the
        answer to ``partial: true``, never a 5xx."""
        from h2o3_tpu.cluster import health as health_mod

        n = int(params.get("events", params.get("count", 200)))
        if not _truthy(params.get("cluster")):
            return health_mod.diagnostics_snapshot(
                cloud=_active_cloud(), events=n)
        cloud = _active_cloud()
        if cloud is None:
            bundle = health_mod.diagnostics_snapshot(events=n)
            return {"kind": "diagnostics_cluster",
                    "nodes": {bundle["node"]: bundle},
                    "partial": False, "errors": {},
                    "now": int(time.time() * 1000)}
        results, errors = cloud.poll_members(
            "diagnostics_snapshot", {"events": n})
        return {
            "kind": "diagnostics_cluster",
            "nodes": {k: results[k] for k in sorted(results)},
            "partial": bool(errors),
            "errors": {k: errors[k] for k in sorted(errors)},
            "now": int(time.time() * 1000),
        }

    def jstack(params):
        """Real per-thread stack dump (util/JStackCollectorTask.java)."""
        import threading
        import traceback as tb

        frames = __import__("sys")._current_frames()
        traces = []
        for t in threading.enumerate():
            stack = tb.format_stack(frames[t.ident]) if t.ident in frames else []
            traces.append({"thread": t.name, "alive": t.is_alive(),
                           "daemon": t.daemon, "stack": stack})
        return {"traces": traces}

    def logs_ep(params):
        from h2o3_tpu.util import log as L

        L.init()
        return {
            "lines": L.recent(int(params.get("count", 1000))),
            "log_file": L.log_file(),
        }

    def logs_download(params):
        from h2o3_tpu.util import log as L

        L.init()
        return ("\n".join(L.recent(100000)) + "\n").encode()

    def watermeter(params):
        """CPU tick counters (api/WaterMeterCpuTicksHandler.java:6); the
        tick reader lives with the cluster heartbeat so the local route,
        the HeartBeat payload and the cross-node proxy report one shape."""
        from h2o3_tpu.cluster.membership import cpu_ticks_payload

        return cpu_ticks_payload()

    def _federated_metrics():
        """(merged_snapshot, nodes, errors) across the live cloud — or the
        local registry labelled under this node's name when no multi-node
        cloud is up, so ``?cluster=true`` has ONE response shape."""
        from h2o3_tpu import cluster
        from h2o3_tpu.util import telemetry

        cloud = _active_cloud()
        if cloud is None:
            local = cluster.local_cloud()
            node = local.info.name if local is not None else (
                telemetry.node_name() or "localhost")
            merged = telemetry.merge_snapshots(
                {node: telemetry.REGISTRY.snapshot()})
            return merged, [node], {}
        results, errors = cloud.poll_members("metrics_snapshot")
        merged = telemetry.merge_snapshots({
            name: (r or {}).get("metrics", {})
            for name, r in results.items()
        })
        return merged, sorted(results), errors

    def metrics_ep(params):
        """Full registry snapshot as JSON (the quantitative face of
        /3/Timeline — counts where the timeline has events).  With
        ``?cluster=true``: every member's registry is scraped over RPC and
        merged with a ``node=`` label (counters also sum into a
        ``node="_cluster"`` aggregate, histogram buckets merge, gauges stay
        per-node); an unreachable member degrades the answer to
        ``partial: true`` — never a 5xx."""
        from h2o3_tpu.util import telemetry

        if not _truthy(params.get("cluster")):
            return {
                "metrics": telemetry.REGISTRY.snapshot(),
                "now": int(time.time() * 1000),
            }
        merged, nodes, errors = _federated_metrics()
        return {
            "metrics": merged,
            "nodes": nodes,
            "errors": errors,
            "partial": bool(errors),
            "now": int(time.time() * 1000),
        }

    def metrics_prometheus(params):
        """Prometheus text exposition v0.0.4 — point a scraper at it.
        ``?cluster=true`` serves the federated merge (node= labels on every
        series) with a comment header naming unreachable members."""
        from h2o3_tpu.util import telemetry

        if not _truthy(params.get("cluster")):
            return (
                telemetry.REGISTRY.prometheus().encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
        merged, nodes, errors = _federated_metrics()
        text = telemetry.snapshot_prometheus(merged)
        if errors:
            head = "".join(
                f"# partial scrape: {name} unreachable ({msg})\n"
                for name, msg in sorted(errors.items()))
            text = head + text
        return text.encode(), "text/plain; version=0.0.4; charset=utf-8"

    r.register("GET", "/3/Metrics", metrics_ep, "telemetry registry (JSON)")
    r.register("GET", "/3/Metrics/prometheus", metrics_prometheus,
               "telemetry registry (Prometheus text exposition)")
    r.register("GET", "/3/Timeline", timeline_ep, "event timeline")
    r.register("GET", "/3/Traces/{trace_id}", traces_ep,
               "per-trace cost ledger (node x category)")
    r.register("GET", "/3/SlowOps", slowops_ep, "slow-op exemplar log")
    r.register("GET", "/3/Diagnostics", diagnostics_ep,
               "support bundle (health, flight ring, slowops, stacks)")
    r.register("GET", "/3/JStack", jstack, "thread dump")
    r.register("GET", "/3/Logs", logs_ep, "recent log lines")
    r.register("GET", "/3/Logs/download", logs_download, "full log download")
    r.register("GET", "/3/WaterMeterCpuTicks", watermeter, "cpu tick meter")
    r.register("GET", "/3/Ping", lambda p: {"ok": True, "now": int(time.time() * 1000)},
               "liveness probe")

    # ---- model introspection (varimp / PDP / trees / word2vec) ------------
    def model_varimp(params, model_id):
        """Variable importances (ModelOutput varimp + /3/Models makeFI)."""
        m = _get_model(model_id)
        fn = getattr(m, "variable_importances", None)
        if fn is None:
            raise RestError(400, f"{m.algo_name} has no variable importances")
        try:
            vi = fn()
        except NotImplementedError as e:
            raise RestError(400, str(e))
        ordered = sorted(vi.items(), key=lambda kv: -kv[1])
        total = sum(v for _, v in ordered) or 1.0
        return {
            "varimp": [
                {"variable": k, "relative_importance": v,
                 "scaled_importance": v / (ordered[0][1] or 1.0),
                 "percentage": v / total}
                for k, v in ordered
            ]
        }

    def partial_dependence(params):
        """Synchronous PDP (api/ModelBuilders makePDP/fetchPDP): for each
        requested column, sweep a grid and average the model's predictions
        over the frame with that column overridden."""
        m = _get_model(params.get("model_id", ""))
        fr = _get_frame(params.get("frame_id", ""))
        cols = params.get("cols") or []
        if isinstance(cols, str):
            if cols.startswith("["):
                try:  # proper JSON first; python-repr fallback second
                    cols = json.loads(cols)
                except json.JSONDecodeError:
                    cols = json.loads(cols.replace("'", '"'))
            else:
                cols = [cols]
        if not cols:
            raise RestError(400, "cols required")
        nbins = int(params.get("nbins", 20))
        out_tables = []
        for col in cols:
            if col not in fr.names:
                raise RestError(404, f"column {col!r} not in frame")
            c = fr.col(col)
            if c.type is ColType.CAT:
                values: List[Any] = list(range(len(c.domain)))
                labels = list(c.domain)
            else:
                v = c.numeric_view()
                lo, hi = float(np.nanmin(v)), float(np.nanmax(v))
                values = list(np.linspace(lo, hi, nbins))
                labels = [f"{x:.6g}" for x in values]
            dom = m.data_info.response_domain if m.is_classifier else None
            mean_resp: List[Any] = []
            per_class: Dict[str, List[float]] = {lv: [] for lv in (dom or [])}
            for val in values:
                cols_copy = []
                for cc in fr.columns:
                    if cc.name == col:
                        if cc.type is ColType.CAT:
                            data = np.full(fr.nrows, val, dtype=np.int32)
                            cols_copy.append(Column(cc.name, data, ColType.CAT, cc.domain))
                        else:
                            data = np.full(fr.nrows, float(val))
                            cols_copy.append(Column(cc.name, data, ColType.NUM))
                    else:
                        cols_copy.append(cc)
                pred = m.predict(Frame(cols_copy))
                if m.is_classifier:
                    # per-class probability curves (the reference's PDP is
                    # per class; averaging one arbitrary column would be
                    # silently wrong for multinomial)
                    for lv in dom:
                        per_class[lv].append(
                            float(np.nanmean(pred.col(f"p{lv}").numeric_view()))
                        )
                else:
                    mean_resp.append(
                        float(np.nanmean(pred.col("predict").numeric_view()))
                    )
            table = {"column": col, "values": labels}
            if m.is_classifier:
                table["classes"] = dom
                table["mean_response_per_class"] = per_class
                # convenience: positive-class curve for binomial
                table["mean_response"] = per_class[dom[-1]]
            else:
                table["mean_response"] = mean_resp
            out_tables.append(table)
        payload = {"partial_dependence_data": out_tables}
        # store for GET /3/PartialDependence/{name} (fetchPDP)
        from h2o3_tpu.api.handlers_ext import PDPResult

        dest = params.get("destination_key") or DKV.make_key("pdp")
        DKV.put(dest, PDPResult(payload))
        payload["destination_key"] = {"name": dest}
        return payload

    def tree_inspect(params, model_id, tree_number):
        """Tree inspection (hex/schemas TreeV3 / h2o-py h2o.tree): node
        arrays of one tree in heap layout."""
        from h2o3_tpu.models.tree.common import TreeModelBase, tree_feature_names

        m = _get_model(model_id)
        if not isinstance(m, TreeModelBase):
            raise RestError(400, f"{m.algo_name} is not a tree model")
        t = int(tree_number)
        cls = int(params.get("tree_class", 0))
        b = m.booster
        if not 0 <= cls < len(b.trees_per_class):
            raise RestError(404, f"tree_class {cls} out of range")
        trees = b.trees_per_class[cls]
        if not 0 <= t < trees.ntrees:
            raise RestError(404, f"tree {t} out of range (ntrees={trees.ntrees})")
        from h2o3_tpu.models.tree.booster import refuse_sets

        try:
            refuse_sets(trees, "/3/Tree (a tree's thresholds)")
        except NotImplementedError as e:
            raise RestError(400, str(e))
        names = tree_feature_names(m.data_info, m.tree_encoding)
        feat = trees.feat[t]
        is_split = trees.is_split[t]
        edges = trees.edges
        import math

        thresholds = []
        for i in range(len(feat)):
            if is_split[i]:
                f, sb = int(feat[i]), int(trees.split_bin[t][i])
                # split 'bins <= sb go left' -> raw threshold = edge[sb];
                # sb == nbins-1 separates non-NA from NA only (no finite
                # threshold), and inf edge padding (low-cardinality
                # features) is not valid JSON — both report null
                if sb >= edges.shape[1]:
                    thresholds.append(None)
                else:
                    e = float(edges[f][sb])
                    thresholds.append(e if math.isfinite(e) else None)
            else:
                thresholds.append(None)
        return {
            "model_id": {"name": model_id},
            "tree_number": t,
            "tree_class": cls,
            "features": [names[int(f)] if is_split[i] else None
                         for i, f in enumerate(feat)],
            "thresholds": thresholds,
            "is_split": [bool(x) for x in is_split],
            "default_left": [bool(x) for x in trees.default_left[t]],
            "predictions": [float(x) for x in trees.leaf[t]],
            "layout": "heap: children of node i are 2i+1 (left) / 2i+2",
        }

    def w2v_synonyms(params):
        """/3/Word2VecSynonyms (word2vec REST extension)."""
        from h2o3_tpu.models.word2vec import Word2VecModel

        m = _get_model(params.get("model_id", ""))
        if not isinstance(m, Word2VecModel):
            raise RestError(400, f"{m.algo_name} is not a word2vec model")
        word = params.get("word")
        if not word:
            raise RestError(400, "word required")
        count = int(params.get("count", 10))
        syn = m.find_synonyms(word, count)
        return {"synonyms": list(syn.keys()), "scores": list(syn.values())}

    def w2v_transform(params):
        """/3/Word2VecTransform: words frame -> embedding frame."""
        from h2o3_tpu.models.word2vec import Word2VecModel

        m = _get_model(params.get("model_id", ""))
        if not isinstance(m, Word2VecModel):
            raise RestError(400, f"{m.algo_name} is not a word2vec model")
        fr = _get_frame(params.get("words_frame", ""))
        agg = params.get("aggregate_method", "none").lower()
        vecs = m.transform(fr, aggregate_method=agg)
        dest = params.get("destination_frame") or DKV.make_key("w2v")
        DKV.put(dest, vecs)
        return {"vectors_frame": {"name": dest}}

    def predict_contribs(params, model_id, frame_id):
        """SHAP contributions over REST (the predict_contributions flag of
        /3/Predictions in the reference)."""
        m = _get_model(model_id)
        fr = _get_frame(frame_id)
        fn = getattr(m, "predict_contributions", None)
        if fn is None:
            raise RestError(400, f"{m.algo_name} has no SHAP contributions")
        try:
            contribs = fn(fr)
        except ValueError as e:
            raise RestError(400, str(e))
        dest = params.get("predictions_frame") or DKV.make_key("contrib")
        contribs.key = dest
        DKV.put(dest, contribs)
        return {"predictions_frame": {"name": dest},
                "columns": contribs.names}

    r.register(
        "POST", "/3/PredictContributions/models/{model_id}/frames/{frame_id}",
        predict_contribs, "SHAP prediction contributions",
    )
    r.register("GET", "/3/Models/{model_id}/varimp", model_varimp,
               "variable importances")
    r.register("POST", "/3/PartialDependence", partial_dependence,
               "partial dependence plot data")
    r.register("GET", "/3/Trees/{model_id}/{tree_number}", tree_inspect,
               "tree node inspection")
    r.register("POST", "/3/Word2VecSynonyms", w2v_synonyms, "word synonyms")
    r.register("POST", "/3/Word2VecTransform", w2v_transform,
               "words -> embeddings")

    # ---- synthetic data + munging utilities -------------------------------
    def create_frame(params):
        """/3/CreateFrame (hex/createframe recipes, simplified)."""
        rows = int(params.get("rows", 10000))
        cols = int(params.get("cols", 10))
        seed = int(params.get("seed", -1))
        rng = np.random.default_rng(None if seed == -1 else seed)
        cat_frac = float(params.get("categorical_fraction", 0.2))
        int_frac = float(params.get("integer_fraction", 0.2))
        bin_frac = float(params.get("binary_fraction", 0.1))
        missing_frac = float(params.get("missing_fraction", 0.0))
        factors = int(params.get("factors", 5))
        real_range = float(params.get("real_range", 100.0))
        has_response = str(params.get("has_response", "false")).lower() in (
            "true", "1", "yes")
        response_factors = int(params.get("response_factors", 2))

        n_cat = int(round(cols * cat_frac))
        n_int = int(round(cols * int_frac))
        n_bin = int(round(cols * bin_frac))
        n_real = max(cols - n_cat - n_int - n_bin, 0)
        out_cols: List[Column] = []
        i = 0
        for _ in range(n_real):
            i += 1
            data = rng.uniform(-real_range, real_range, rows)
            if missing_frac:
                data[rng.random(rows) < missing_frac] = np.nan
            out_cols.append(Column(f"C{i}", data, ColType.NUM))
        for _ in range(n_int):
            i += 1
            data = rng.integers(-100, 100, rows).astype(np.float64)
            if missing_frac:
                data[rng.random(rows) < missing_frac] = np.nan
            out_cols.append(Column(f"C{i}", data, ColType.NUM))
        for _ in range(n_bin):
            i += 1
            data = (rng.random(rows) < 0.5).astype(np.float64)
            if missing_frac:
                data[rng.random(rows) < missing_frac] = np.nan
            out_cols.append(Column(f"C{i}", data, ColType.NUM))
        for _ in range(n_cat):
            i += 1
            dom = [f"c{i}.l{j}" for j in range(factors)]
            codes = rng.integers(0, factors, rows).astype(np.int32)
            if missing_frac:
                codes[rng.random(rows) < missing_frac] = -1
            out_cols.append(Column(f"C{i}", codes, ColType.CAT, dom))
        if has_response:
            if response_factors > 1:
                dom = [f"r{j}" for j in range(response_factors)]
                codes = rng.integers(0, response_factors, rows).astype(np.int32)
                out_cols.insert(0, Column("response", codes, ColType.CAT, dom))
            else:
                out_cols.insert(
                    0, Column("response", rng.normal(size=rows), ColType.NUM)
                )
        dest = params.get("dest") or params.get("destination_frame") or DKV.make_key("frame")
        fr = Frame(out_cols)
        fr.key = dest
        DKV.put(dest, fr)
        return {"destination_frame": {"name": dest},
                "rows": fr.nrows, "cols": fr.ncols}

    def missing_inserter(params):
        """/3/MissingInserter: punch NAs into a frame in place."""
        key = params.get("dataset") or params.get("frame_id") or ""
        fr = _get_frame(key)
        frac = float(params.get("fraction", 0.1))
        seed = int(params.get("seed", -1))
        rng = np.random.default_rng(None if seed == -1 else seed)
        new_cols = []
        for c in fr.columns:
            mask = rng.random(fr.nrows) < frac
            if c.type is ColType.CAT:
                data = np.where(mask, -1, c.data).astype(np.int32)
                new_cols.append(Column(c.name, data, ColType.CAT, c.domain))
            elif c.type in (ColType.NUM, ColType.TIME):
                data = np.where(mask, np.nan, c.data.astype(np.float64))
                new_cols.append(Column(c.name, data, c.type))
            else:
                data = c.data.copy()
                data[mask] = None
                new_cols.append(Column(c.name, data, c.type))
        out = Frame(new_cols)
        out.key = key
        DKV.put(key, out)
        return {"frame_id": {"name": key}}

    r.register("POST", "/3/CreateFrame", create_frame, "synthetic frame")
    r.register("POST", "/3/MissingInserter", missing_inserter, "insert NAs")

    # ---- schema metadata (water/api/SchemaMetadata -> bindings codegen) ---
    def _schema_of(pcls) -> Dict[str, Any]:
        return {
            "name": pcls.__name__,
            "fields": [
                {
                    "name": f.name,
                    "type": str(f.type),
                    "default_value": _default_of(f),
                }
                for f in dataclasses.fields(pcls)
            ],
        }

    def schemas_list(params):
        return {"schemas": [
            _schema_of(pcls) for _, pcls in sorted(
                (a, p) for a, (_, p) in algos.items()
            )
        ]}

    def schema_get(params, name):
        for a, (_, pcls) in algos.items():
            if pcls.__name__ == name or a == name.lower():
                return {"schemas": [_schema_of(pcls)]}
        raise RestError(404, f"no schema {name!r}")

    r.register("GET", "/3/Metadata/schemas", schemas_list, "parameter schemas")
    r.register("GET", "/3/Metadata/schemas/{name}", schema_get, "one schema")

    # ---- Flow-lite (h2o-web: the notebook UI, here a minimal live console)
    _FLOW_HTML = """<!DOCTYPE html>
<html><head><title>h2o3-tpu Flow</title>
<style>
 body{font-family:monospace;margin:2em;background:#fafafa;color:#222}
 h1{font-size:1.3em} h2{font-size:1.05em;margin-top:1.4em}
 table{border-collapse:collapse;margin:.5em 0}
 td,th{border:1px solid #ccc;padding:.25em .6em;text-align:left}
 .muted{color:#888}
</style></head>
<body>
<h1>h2o3-tpu <span class=muted>Flow-lite</span></h1>
<div id=cloud class=muted>loading&hellip;</div>
<h2>Notebook <span class=muted>(Rapids cells — see /99/Rapids/help)</span></h2>
<div id=history></div>
<div><textarea id=cell rows=3 cols=80
 placeholder="(sort frame_id [0] [1])"></textarea><br>
<button id=run>Run</button>
<input id=fname size=18 placeholder="flow name">
<button id=fsave>Save flow</button>
<select id=flist></select>
<button id=fload>Load</button>
<button id=freplay>Load + replay</button>
<span class=muted>flows persist server-side under
 /3/NodePersistentStorage/notebook</span></div>
<pre id=cellout class=muted></pre>
<h2>Import <span class=muted>(path/glob/URI on the server)</span></h2>
<div><input id=ipath size=60 placeholder="/data/train.csv">
<input id=iname size=20 placeholder="frame name (optional)">
<button id=imp>Import &amp; parse</button></div>
<pre id=impout class=muted></pre>
<h2>Train</h2>
<div><select id=talgo></select>
<input id=tframe size=20 placeholder="training frame">
<input id=tresp size=14 placeholder="response col">
<input id=tparams size=40 placeholder='extra params JSON, e.g. {"ntrees":20}'>
<button id=train>Train</button></div>
<pre id=trainout class=muted></pre>
<h2>Frames</h2><table id=frames></table>
<h2>Models</h2><table id=models></table>
<h2>Jobs</h2><table id=jobs></table>
<script>
async function j(p){const r=await fetch(p);return r.json()}
async function post(p,body){const r=await fetch(p,{method:'POST',
 headers:{'Content-Type':'application/json'},body:JSON.stringify(body)});
 return r.json()}
function show(id,v){document.getElementById(id).textContent=
 typeof v==='string'?v:JSON.stringify(v,null,1)}
let cells=[];
function renderHistory(){
 const h=document.getElementById('history');h.innerHTML='';
 cells.forEach((c,i)=>{
  const d=document.createElement('div');
  const inp=document.createElement('pre');
  inp.textContent='['+(i+1)+'] '+c.input;d.appendChild(inp);
  const out=document.createElement('pre');out.className='muted';
  out.textContent=typeof c.output==='string'?c.output:
   JSON.stringify(c.output,null,1);d.appendChild(out);
  h.appendChild(d)})}
async function runCell(ast){
 const out=await post('/99/Rapids',{ast});
 cells.push({input:ast,output:out});renderHistory();return out}
async function refreshFlows(){
 const sel=document.getElementById('flist');sel.innerHTML='';
 const ls=await j('/3/NodePersistentStorage/notebook');
 for(const e of (ls.entries||[])){
  const o=document.createElement('option');o.value=e.name;
  o.textContent=e.name;sel.appendChild(o)}}
async function loadFlow(replay){
 const name=document.getElementById('flist').value;if(!name)return;
 const r=await fetch('/3/NodePersistentStorage/notebook/'+
  encodeURIComponent(name));
 const doc=JSON.parse(await r.text());
 if(replay){cells=[];renderHistory();
  for(const c of (doc.cells||[]))await runCell(c.input)}
 else{cells=doc.cells||[];renderHistory()}
 document.getElementById('fname').value=name;refresh()}
document.addEventListener('DOMContentLoaded',()=>{
 document.getElementById('run').onclick=async()=>{
  const ast=document.getElementById('cell').value.trim();
  if(!ast)return;
  show('cellout',await runCell(ast));
  document.getElementById('cell').value='';refresh()};
 document.getElementById('fsave').onclick=async()=>{
  const name=document.getElementById('fname').value.trim();
  if(!name){show('cellout','name the flow first');return}
  await post('/3/NodePersistentStorage/notebook/'+
   encodeURIComponent(name),
   {value:JSON.stringify({version:1,cells})});
  show('cellout','saved flow '+name);refreshFlows()};
 document.getElementById('fload').onclick=()=>loadFlow(false);
 document.getElementById('freplay').onclick=()=>loadFlow(true);
 refreshFlows();
 document.getElementById('imp').onclick=async()=>{
  const path=document.getElementById('ipath').value.trim();
  if(!path)return;
  const up=await post('/3/ImportFiles',{path});
  if(up.http_status){show('impout',up);return}
  const dest=document.getElementById('iname').value.trim()||undefined;
  const srcs=up.destination_frames?up.destination_frames:[up.destination_frame];
  show('impout',await post('/3/Parse',
   {source_frames:srcs,destination_frame:dest}));refresh()};
 document.getElementById('train').onclick=async()=>{
  const algo=document.getElementById('talgo').value;
  let extra={};
  const t=document.getElementById('tparams').value.trim();
  if(t){try{extra=JSON.parse(t)}catch(e){show('trainout','bad JSON: '+e);return}}
  const body=Object.assign({
   training_frame:document.getElementById('tframe').value.trim(),
   response_column:document.getElementById('tresp').value.trim()||undefined},
   extra);
  show('trainout','training…');
  show('trainout',await post('/3/ModelBuilders/'+algo,body));refresh()};
 j('/3/ModelBuilders').then(b=>{
  const sel=document.getElementById('talgo');
  for(const a of Object.keys(b.model_builders).sort()){
   const o=document.createElement('option');o.value=a;o.textContent=a;
   sel.appendChild(o)}});
});
function row(t,cells,th){const tr=document.createElement('tr');
 for(const c of cells){const td=document.createElement(th?'th':'td');
  td.textContent=c;tr.appendChild(td)} t.appendChild(tr)}
async function refresh(){
 const c=await j('/3/Cloud');
 document.getElementById('cloud').textContent=
  c.cloud_name+' — '+c.version+' — devices: '+(c.devices||[]).join(', ');
 const f=document.getElementById('frames');f.innerHTML='';
 row(f,['frame','rows','cols'],true);
 for(const fr of (await j('/3/Frames')).frames)
  row(f,[fr.frame_id.name,fr.rows,fr.num_columns]);
 const m=document.getElementById('models');m.innerHTML='';
 row(m,['model','algo'],true);
 for(const mo of (await j('/3/Models')).models)
  row(m,[mo.model_id.name,mo.algo]);
 const jb=document.getElementById('jobs');jb.innerHTML='';
 row(jb,['job','status','progress','description'],true);
 for(const job of (await j('/3/Jobs')).jobs)
  row(jb,[job.key.name,job.status,Math.round(job.progress*100)+'%',job.description]);
}
refresh();setInterval(refresh,5000);
</script></body></html>"""

    def flow_page(params):
        # (bytes, content-type): the server renders it as HTML, not a
        # download (the plain-bytes branch is octet-stream for models)
        return (_FLOW_HTML.encode(), "text/html; charset=utf-8")

    r.register("GET", "/", flow_page, "Flow-lite console")
    r.register("GET", "/flow/index.html", flow_page, "Flow-lite console")

    # ---- round-4 route groups (ModelMetrics CRUD, model io by URI, NPS,
    # munging utilities, diagnostics) — registered last so they see the
    # fully-populated registry for dispatch-based reuse ----------------------
    from h2o3_tpu.api import handlers_ext, handlers_ops

    handlers_ops.register(r, server)
    handlers_ext.register(r, server)
