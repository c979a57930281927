"""Wire messages.  `elasticdl_pb2.py` and `serving_pb2.py` are checked in
and are the artifacts every process imports; nothing is generated at
import.  After editing a .proto, regenerate explicitly:
`scripts/gen_elasticdl_pb2.sh` / `python scripts/gen_serving_pb2.py`."""

from elasticdl_tpu.proto import elasticdl_pb2  # noqa: F401
