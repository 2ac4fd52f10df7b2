# CPU test/dev image (role parity with the reference's Dockerfile, which
# baked TF 1.10 + Spark for local[2] testing). TPU execution uses a TPU-VM
# image instead — this container runs the full suite on the virtual 8-device
# CPU mesh.
FROM python:3.12-slim AS base

RUN apt-get update && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY sparkflow_tpu ./sparkflow_tpu
COPY tests ./tests
COPY examples ./examples

RUN pip install --no-cache-dir "jax[cpu]" optax orbax-checkpoint chex dill pytest \
    && pip install --no-cache-dir -e .

ENV JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8

CMD ["python", "-m", "pytest", "tests/", "-q"]

# `docker compose` services build this target: JRE + pyspark baked in once so
# the standalone cluster / pyspark e2e suite starts without network installs
FROM base AS pyspark
RUN apt-get update && apt-get install -y --no-install-recommends default-jre \
    && rm -rf /var/lib/apt/lists/* \
    && pip install --no-cache-dir pyspark==3.5.1
