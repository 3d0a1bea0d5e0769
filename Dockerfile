# gubernator-tpu server image (reference: the Go repo's multi-stage
# Dockerfile; here the runtime is Python + JAX, so one stage suffices).
#
# The one installation this code is written and tested against: Python 3.12,
# jax/jaxlib 0.9.0, numpy 2.0.2 (pyproject.toml pins the same jax). On a
# TPU host add libtpu 0.0.34 (jax[tpu]==0.9.0), the release chip_smoke.py
# last passed on.
ARG BASE_IMAGE=python:3.12-slim
FROM ${BASE_IMAGE}

WORKDIR /opt/gubernator-tpu

RUN pip install --no-cache-dir \
    "jax==0.9.0" "numpy==2.0.2" aiohttp grpcio protobuf prometheus_client xxhash

COPY gubernator_tpu/ ./gubernator_tpu/
COPY example.conf ./

ENV PYTHONPATH=/opt/gubernator-tpu
ENV GUBER_GRPC_ADDRESS=0.0.0.0:1051
ENV GUBER_HTTP_ADDRESS=0.0.0.0:1050

EXPOSE 1050 1051 7946

# k8s probes: python -m gubernator_tpu.cmd.healthcheck
ENTRYPOINT ["python", "-m", "gubernator_tpu"]
