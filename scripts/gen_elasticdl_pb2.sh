#!/usr/bin/env bash
# Regenerate the checked-in elasticdl_tpu/proto/elasticdl_pb2.py after
# editing elasticdl.proto (serving.proto: python scripts/gen_serving_pb2.py).
set -euo pipefail
cd "$(dirname "$0")/../elasticdl_tpu/proto"
protoc --python_out=. --proto_path=. elasticdl.proto
