# Build + deploy image for gubernator-tpu (reference: Dockerfile, which
# builds static Go binaries; here the runtime is Python/JAX so the deploy
# image is a slim Python base with the package installed).
#
# The default install runs the CPU backend of XLA — right for development
# clusters — and says so by name (GUBER_TPU_PLATFORM=cpu below): a daemon
# that finds no TPU refuses to fall back to the CPU silently. On TPU
# hosts, build with
#   --build-arg JAX_EXTRA="jax[tpu]" --build-arg GUBER_TPU_PLATFORM=
# (pulls libtpu; the daemon finds the chips automatically).
FROM python:3.12-slim AS build

ARG JAX_EXTRA=""

# g++ builds the native slotmap (the host-side key→slot table).
RUN apt-get update && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY pyproject.toml README.md ./
COPY gubernator_tpu ./gubernator_tpu

# The runtime image has no compiler, and the loader rebuilds a library
# that is older than its source: installed files get arbitrary mtimes,
# so stamp the libraries last.
RUN make -C gubernator_tpu/native \
    && pip install --no-cache-dir --prefix=/install . ${JAX_EXTRA} \
    && find /install -name 'libguber_*.so' -exec touch {} +

FROM python:3.12-slim

ARG GUBER_TPU_PLATFORM=cpu
ENV GUBER_TPU_PLATFORM=${GUBER_TPU_PLATFORM}

COPY --from=build /install /usr/local

# Container healthcheck probes /v1/HealthCheck on the local daemon
# (reference Dockerfile HEALTHCHECK, cmd/healthcheck). The probe is a
# Python process that imports the package (~2s); the timeout must cover
# that, not just the HTTP round trip.
HEALTHCHECK --interval=10s --timeout=5s --start-period=60s --retries=2 \
    CMD [ "gubernator-tpu-healthcheck" ]

ENTRYPOINT ["gubernator-tpu"]

# HTTP / gRPC / memberlist gossip (reference exposes the same three).
EXPOSE 80
EXPOSE 81
EXPOSE 7946
