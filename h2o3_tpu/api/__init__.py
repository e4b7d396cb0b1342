"""REST API v3 — the wire surface clients speak.

Reference: ``water/api/`` (~25k LoC: RequestServer route registry +
dispatch, RequestServer.java:56-80,241; 125 v3 endpoints registered in
RegisterV3Api.java; schema/handler pattern under api/schemas3/), served by
the ``h2o-webserver-iface`` facade over Jetty.

TPU-native: an asyncio event-loop front-end (``server.py``) with admission
control (connection cap, per-route budgets, bounded queue — overload sheds
429 + Retry-After) and coalesced batched scoring (``coalesce.py``: same-model
predictions collect for a window and execute as one devcache-warm dispatch);
the cluster control plane is host-side Python, device compute stays in
jitted programs.  The same versioned route layout (/3/..., /99/Rapids) and
JSON responses shaped like the reference's schema objects so h2o-py-style
clients port over.
"""

from h2o3_tpu.api.server import H2OServer, start_server

__all__ = ["H2OServer", "start_server"]
