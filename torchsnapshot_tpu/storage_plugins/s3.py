"""S3 storage plugin — self-contained REST client, no botocore required.

Reference analogue: ``torchsnapshot/storage_plugins/s3.py:18-80`` (aiobotocore
put/get with HTTP Range reads, inclusive-end correction at s3.py:60-66).
This environment ships no boto3/aiobotocore, so the plugin speaks the S3 REST
API directly over ``requests`` with SigV4 request signing:

- ``PUT /key`` uploads (unsigned payload hash, so no extra pass over bytes)
- ``GET /key`` with ``Range: bytes=a-b`` (inclusive end, corrected here the
  same way the reference does)
- ``DELETE /key`` and ListObjectsV2 for delete_dir
- modest retries on 5xx/connection errors

Endpoint resolution: ``TPUSNAP_S3_ENDPOINT`` (e.g. ``http://127.0.0.1:9000``
for the in-suite fake server or any S3-compatible store; path-style
``/bucket/key`` addressing), else virtual-host style
``https://{bucket}.s3.{region}.amazonaws.com``.  Credentials come from the
standard ``AWS_ACCESS_KEY_ID``/``AWS_SECRET_ACCESS_KEY``/``AWS_SESSION_TOKEN``
env vars; requests go unsigned when none are set (local fakes don't check).
"""

from __future__ import annotations

import asyncio
import datetime
import hashlib
import hmac
import os
import threading
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional
from xml.etree import ElementTree

from .. import knobs, retry
from ..io_types import ReadIO, StoragePlugin, WriteIO, contiguous

_IO_THREADS = 16
# Shared taxonomy (retry.py): same status set every retry layer classifies.
_TRANSIENT_STATUS = retry.TRANSIENT_HTTP_STATUS
_MAX_ATTEMPTS = 5
# Shared backoff policy parameters for this plugin's internal attempt loops
# (retry.backoff_s): quick ramp, low cap — S3 throttling clears fast and the
# scheduler holds the longer-horizon budget above us.
_BACKOFF_BASE_S = 0.2
_BACKOFF_CAP_S = 2.0
_UNSIGNED_PAYLOAD = "UNSIGNED-PAYLOAD"

# AWS rejects single PUTs over 5 GB; payloads past the threshold go through
# multipart upload instead.  Normal checkpoint payloads stay far below this
# (512 MB chunk/shard knobs), but an oversized pickled object or a merged
# slab must not fail outright.  Env-overridable so tests can exercise the
# multipart path with small objects.
_DEFAULT_MULTIPART_THRESHOLD = 5 * 1024 * 1024 * 1024
_DEFAULT_MULTIPART_PART = 256 * 1024 * 1024  # AWS bounds: >=5 MB, <=10k parts


def _hmac_sha256(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


class _SigV4:
    """Minimal AWS Signature Version 4 signer for S3 (UNSIGNED-PAYLOAD)."""

    def __init__(
        self,
        access_key: str,
        secret_key: str,
        session_token: Optional[str],
        region: str,
    ) -> None:
        self._access_key = access_key
        self._secret_key = secret_key
        self._session_token = session_token
        self._region = region

    def sign(self, method: str, url: str, headers: Dict[str, str]) -> None:
        parsed = urllib.parse.urlsplit(url)
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        date_stamp = now.strftime("%Y%m%d")

        headers["host"] = parsed.netloc
        headers["x-amz-date"] = amz_date
        headers["x-amz-content-sha256"] = _UNSIGNED_PAYLOAD
        if self._session_token:
            headers["x-amz-security-token"] = self._session_token

        signed_names = sorted(k.lower() for k in headers)
        canonical_headers = "".join(
            f"{name}:{str(headers[_orig(headers, name)]).strip()}\n"
            for name in signed_names
        )
        canonical_query = "&".join(
            sorted(
                f"{urllib.parse.quote(k, safe='')}={urllib.parse.quote(v, safe='')}"
                for k, v in urllib.parse.parse_qsl(
                    parsed.query, keep_blank_values=True
                )
            )
        )
        canonical_request = "\n".join(
            [
                method,
                # The request path is already percent-encoded; S3 is the one
                # AWS service that forbids double-encoding in the canonical
                # path, so use it verbatim.
                parsed.path or "/",
                canonical_query,
                canonical_headers,
                ";".join(signed_names),
                _UNSIGNED_PAYLOAD,
            ]
        )
        scope = f"{date_stamp}/{self._region}/s3/aws4_request"
        string_to_sign = "\n".join(
            [
                "AWS4-HMAC-SHA256",
                amz_date,
                scope,
                hashlib.sha256(canonical_request.encode()).hexdigest(),
            ]
        )
        key = _hmac_sha256(f"AWS4{self._secret_key}".encode(), date_stamp)
        key = _hmac_sha256(key, self._region)
        key = _hmac_sha256(key, "s3")
        key = _hmac_sha256(key, "aws4_request")
        signature = hmac.new(key, string_to_sign.encode(), hashlib.sha256).hexdigest()
        headers["Authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={self._access_key}/{scope}, "
            f"SignedHeaders={';'.join(signed_names)}, Signature={signature}"
        )


def _orig(headers: Dict[str, str], lower_name: str) -> str:
    for k in headers:
        if k.lower() == lower_name:
            return k
    raise KeyError(lower_name)


class S3StoragePlugin(StoragePlugin):
    # Per-call configuration accepted via storage_options (reference
    # storage_plugin.py:20-53 threads an options dict to constructors);
    # each key overrides its env-var equivalent for THIS plugin instance.
    _KNOWN_OPTIONS = frozenset(
        {"endpoint", "region", "access_key", "secret_key", "session_token"}
    )

    def __init__(
        self, root: str, storage_options: Optional[Dict[str, str]] = None
    ) -> None:
        import requests

        options = dict(storage_options or {})
        unknown = set(options) - self._KNOWN_OPTIONS
        if unknown:
            raise ValueError(
                f"Unknown s3 storage_options: {sorted(unknown)} "
                f"(supported: {sorted(self._KNOWN_OPTIONS)})"
            )
        self._requests = requests
        bucket, _, prefix = root.partition("/")
        self.bucket = bucket
        self.prefix = prefix.strip("/")
        self._executor: Optional[ThreadPoolExecutor] = None
        self._executor_lock = threading.Lock()
        self._delete_executor = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="s3_del"
        )
        # Child pool for intra-object ranged-GET fan-out: the parent read
        # occupies an s3_io thread and blocks on its chunks, so submitting
        # chunks to the same pool deadlocks once every io thread holds a
        # parent read (same parent/child split as fs.py's chunk reads).
        # Sized above the 16-thread io pool: with all 16 parents fanning
        # out, a smaller pool would cap aggregate in-flight requests BELOW
        # the 16 single streams it replaces.  Built eagerly — this is
        # reached from io-pool worker threads where lazy init would race.
        self._chunk_executor = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix="s3_chunk"
        )
        region = options.get(
            "region",
            os.environ.get(
                "AWS_REGION", os.environ.get("AWS_DEFAULT_REGION", "us-east-1")
            ),
        )
        endpoint = options.get("endpoint", knobs.get_s3_endpoint())
        if endpoint:
            # Path-style addressing for custom endpoints (fakes, minio).
            self._base = f"{endpoint.rstrip('/')}/{bucket}"
        else:
            self._base = f"https://{bucket}.s3.{region}.amazonaws.com"
        access_key = options.get("access_key", os.environ.get("AWS_ACCESS_KEY_ID"))
        secret_key = options.get(
            "secret_key", os.environ.get("AWS_SECRET_ACCESS_KEY")
        )
        self._signer: Optional[_SigV4] = None
        if access_key and secret_key:
            self._signer = _SigV4(
                access_key,
                secret_key,
                options.get("session_token", os.environ.get("AWS_SESSION_TOKEN")),
                region,
            )
        # One session per executor thread: requests.Session is not
        # thread-safe under concurrent use (same pattern as gcs.py).
        self._local = threading.local()

    def _session(self):
        if not hasattr(self._local, "session"):
            self._local.session = self._requests.Session()
        return self._local.session

    def _get_executor(self) -> ThreadPoolExecutor:
        # Double-checked under a lock: the sync_* surface is driven from
        # multiple caller threads (replication workers), where an unlocked
        # check-then-set would build two pools and leak one.
        if self._executor is None:
            with self._executor_lock:
                if self._executor is None:
                    self._executor = ThreadPoolExecutor(
                        max_workers=_IO_THREADS, thread_name_prefix="s3_io"
                    )
        return self._executor

    def _get_delete_executor(self) -> ThreadPoolExecutor:
        # Child pool for delete_dir's per-key fan-out; see delete_dir.
        # Built eagerly in __init__ (unlike _get_executor, this getter runs
        # on I/O-pool worker threads, where a lazy check-then-set races and
        # leaks a pool); construction is cheap — threads spawn on first
        # submit.
        return self._delete_executor

    def _key(self, path: str) -> str:
        return f"{self.prefix}/{path}" if self.prefix else path

    def _url(self, key: str, query: str = "") -> str:
        url = f"{self._base}/{urllib.parse.quote(key, safe='/')}"
        return f"{url}?{query}" if query else url

    def _request(self, method: str, url: str, *, data=None, headers=None):
        headers = dict(headers or {})
        last_exc: Optional[BaseException] = None
        for attempt in range(_MAX_ATTEMPTS):
            if attempt:
                from ..telemetry import metrics as tmetrics

                tmetrics.record_retry("s3")
                retry.sleep_backoff(
                    attempt, base_s=_BACKOFF_BASE_S, cap_s=_BACKOFF_CAP_S
                )
            req_headers = dict(headers)
            if self._signer is not None:
                self._signer.sign(method, url, req_headers)
            try:
                resp = self._session().request(
                    method, url, data=data, headers=req_headers, timeout=300
                )
            except (
                self._requests.exceptions.ConnectionError,
                self._requests.exceptions.Timeout,
                self._requests.exceptions.ChunkedEncodingError,
            ) as e:
                last_exc = e
                continue
            if resp.status_code in _TRANSIENT_STATUS:
                last_exc = RuntimeError(
                    f"S3 transient {resp.status_code}: {resp.text[:200]}"
                )
                continue
            return resp
        raise RuntimeError(f"S3 request failed after {_MAX_ATTEMPTS} attempts") from (
            last_exc
        )

    # ------------------------------------------------------------- plugin API

    async def write(self, write_io: WriteIO) -> None:
        def _put() -> None:
            # memoryview body: requests uploads it without copying (the old
            # MemoryviewStream behavior), and retries re-send the same view.
            body = memoryview(contiguous(write_io.buf))
            threshold = knobs.get_s3_multipart_threshold_bytes(
                _DEFAULT_MULTIPART_THRESHOLD
            )
            if body.nbytes > threshold:
                self._multipart_put(self._key(write_io.path), body)
                return
            resp = self._request(
                "PUT", self._url(self._key(write_io.path)), data=body
            )
            if resp.status_code not in (200, 201):
                raise RuntimeError(
                    f"S3 PUT {write_io.path} failed: {resp.status_code} "
                    f"{resp.text[:200]}"
                )

        await asyncio.get_running_loop().run_in_executor(self._get_executor(), _put)

    def _initiate_multipart(self, key: str) -> str:
        """POST ?uploads → url-quoted UploadId (raises on failure)."""
        resp = self._request("POST", self._url(key, "uploads"))
        if resp.status_code != 200:
            raise RuntimeError(
                f"S3 initiate multipart for {key} failed: "
                f"{resp.status_code} {resp.text[:200]}"
            )
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        tree = ElementTree.fromstring(resp.content)
        upload_el = tree.find(f"{ns}UploadId")
        if upload_el is None:  # fakes may omit the namespace
            upload_el = tree.find("UploadId")
        if upload_el is None or not upload_el.text:
            raise RuntimeError(f"S3 initiate multipart for {key}: no UploadId")
        return urllib.parse.quote(upload_el.text, safe="")

    def _complete_multipart(self, key: str, upload_id: str, etags) -> None:
        complete = (
            "<CompleteMultipartUpload>"
            + "".join(
                f"<Part><PartNumber>{n}</PartNumber>"
                f"<ETag>{etag}</ETag></Part>"
                for n, etag in etags
            )
            + "</CompleteMultipartUpload>"
        ).encode()
        resp = self._request(
            "POST", self._url(key, f"uploadId={upload_id}"), data=complete
        )
        # Complete can return 200 with an <Error> body (same documented
        # AWS behavior CopyObject has): require the success element.
        if (
            resp.status_code != 200
            or b"CompleteMultipartUploadResult" not in resp.content
        ):
            raise RuntimeError(
                f"S3 complete multipart for {key} failed: "
                f"{resp.status_code} {resp.text[:200]}"
            )

    def _abort_multipart(self, key: str, upload_id: str) -> None:
        """Best-effort: an un-aborted upload's parts are billed forever."""
        try:
            self._request("DELETE", self._url(key, f"uploadId={upload_id}"))
        except Exception:
            pass

    def _multipart_put(self, key: str, body: memoryview) -> None:
        """Multipart upload for payloads over the single-PUT ceiling.

        Parts are memoryview slices (no copy) sent sequentially on this
        write's executor thread — concurrency across payloads already comes
        from the scheduler's 16-way write fan-out, and each part rides
        ``_request``'s retry loop independently (a transient mid-upload only
        re-sends that part, not the whole object).  On any failure the
        upload is aborted so S3 doesn't bill for orphaned parts."""
        part_size = knobs.get_s3_multipart_part_bytes(_DEFAULT_MULTIPART_PART)
        # AWS caps multipart uploads at 10k parts.
        part_size = max(part_size, -(-body.nbytes // 10000))
        upload_id = self._initiate_multipart(key)
        try:
            etags = []
            for number, offset in enumerate(
                range(0, body.nbytes, part_size), start=1
            ):
                part = body[offset : offset + part_size]
                resp = self._request(
                    "PUT",
                    self._url(
                        key, f"partNumber={number}&uploadId={upload_id}"
                    ),
                    data=part,
                )
                if resp.status_code != 200:
                    raise RuntimeError(
                        f"S3 part {number} of {key} failed: "
                        f"{resp.status_code} {resp.text[:200]}"
                    )
                etags.append((number, resp.headers.get("ETag", "")))
            self._complete_multipart(key, upload_id, etags)
        except BaseException:
            self._abort_multipart(key, upload_id)
            raise

    def _stream_get_into(
        self,
        path: str,
        start: Optional[int],
        end: Optional[int],
        view,
        version: Optional[str] = None,
        cancel=None,
    ) -> None:
        """One GET streamed straight into the caller's view — no
        resp.content staging (with up to 32 concurrent chunks, fully
        buffered responses would hold whole chunk copies outside the
        scheduler's memory budget, plus an extra memcpy pass).  ``start``
        ``end`` (exclusive) select a range; ``(None, None)`` streams the
        whole object, which must be exactly ``view.nbytes`` long.

        Owns its retry loop instead of riding ``_request``: transient
        errors can surface mid-body here, after ``_request`` would already
        have returned."""
        expected = view.nbytes
        url = self._url(self._key(path))
        last_exc: Optional[BaseException] = None
        for attempt in range(_MAX_ATTEMPTS):
            if cancel is not None and cancel.is_set():
                # A sibling fan-out chunk failed hard: abandon the retry
                # schedule instead of making the caller wait it out.
                raise RuntimeError(
                    f"S3 GET {path} abandoned: a sibling chunk failed"
                )
            if attempt:
                from ..telemetry import metrics as tmetrics

                tmetrics.record_retry("s3")
                retry.sleep_backoff(
                    attempt,
                    base_s=_BACKOFF_BASE_S,
                    cap_s=_BACKOFF_CAP_S,
                    cancel=cancel,
                )
            req_headers = {}
            if start is not None:
                req_headers["Range"] = f"bytes={start}-{end - 1}"
            if version is not None:
                # Version pin for fan-out chunks: a concurrent overwrite
                # must fail the read (412), never interleave two versions'
                # bytes into one buffer.
                req_headers["If-Match"] = version
            if self._signer is not None:
                self._signer.sign("GET", url, req_headers)
            try:
                with self._session().get(
                    url, headers=req_headers, timeout=300, stream=True
                ) as resp:
                    if resp.status_code == 412:
                        raise RuntimeError(
                            f"S3 object {path} changed mid-read "
                            f"(ETag no longer {version})"
                        )
                    if resp.status_code in _TRANSIENT_STATUS:
                        last_exc = RuntimeError(
                            f"S3 transient {resp.status_code}"
                        )
                        continue
                    if resp.status_code not in (200, 206):
                        raise RuntimeError(
                            f"S3 GET {path} failed: {resp.status_code} "
                            f"{resp.text[:200]}"
                        )
                    clen = resp.headers.get("Content-Length")
                    if resp.status_code == 200 and start is not None:
                        # A server legally may ignore Range and return 200
                        # with the full object.  A mid-object chunk's body
                        # would start at offset 0, not ``start``; an
                        # offset-0 chunk's body is acceptable only when a
                        # Content-Length proves it is exactly the
                        # requested prefix (i.e. the whole object).
                        if start > 0 or clen is None or int(clen) != expected:
                            raise RuntimeError(
                                f"S3 ignored Range for {path} "
                                f"(200 for bytes={start}-{end - 1})"
                            )
                    if clen is not None and int(clen) != expected:
                        raise RuntimeError(
                            f"S3 GET {path} returned {clen} bytes, "
                            f"expected {expected} "
                            f"(status {resp.status_code})"
                        )
                    filled = 0
                    # 8 MB pieces: each iter_content piece is a GIL bounce
                    # plus a memcpy into the view, and 1 MB pieces held the
                    # restore path well under the transport's line rate.
                    # Cancel latency stays bounded at one piece.
                    for piece in resp.iter_content(chunk_size=8 << 20):
                        if cancel is not None and cancel.is_set():
                            # Mirror the GCS between-chunk check: a
                            # sibling's hard failure must not wait out
                            # this stream's full remaining transfer.
                            raise RuntimeError(
                                f"S3 GET {path} abandoned: a sibling "
                                f"chunk failed"
                            )
                        n = len(piece)
                        if filled + n > expected:
                            raise RuntimeError(
                                f"S3 GET {path} exceeded the expected "
                                f"{expected} bytes"
                            )
                        view[filled : filled + n] = piece
                        filled += n
                    if filled != expected:
                        raise RuntimeError(
                            f"S3 GET {path} returned {filled} "
                            f"bytes, expected {expected} "
                            f"(status {resp.status_code})"
                        )
                    return
            except (
                self._requests.exceptions.ConnectionError,
                self._requests.exceptions.Timeout,
                self._requests.exceptions.ChunkedEncodingError,
            ) as e:
                last_exc = e
                continue
        raise RuntimeError(
            f"S3 GET {path} failed after {_MAX_ATTEMPTS} attempts"
        ) from last_exc

    def _object_stat(self, path: str):
        """(size, etag) from one HEAD — the etag pins fan-out reads to a
        single object version (If-Match on every ranged GET)."""
        resp = self._request("HEAD", self._url(self._key(path)))
        if resp.status_code != 200:
            raise RuntimeError(f"S3 HEAD {path} failed: {resp.status_code}")
        return (
            int(resp.headers.get("Content-Length", -1)),
            resp.headers.get("ETag") or None,
        )

    async def read(self, read_io: ReadIO) -> None:
        def _single_read() -> bytearray:
            headers = {}
            byte_range = read_io.byte_range
            if byte_range is not None:
                start, end = byte_range
                # HTTP Range is inclusive on both ends (reference s3.py:60-66)
                headers["Range"] = f"bytes={start}-{end - 1}"
            resp = self._request(
                "GET", self._url(self._key(read_io.path)), headers=headers
            )
            if resp.status_code not in (200, 206):
                raise RuntimeError(
                    f"S3 GET {read_io.path} failed: {resp.status_code} "
                    f"{resp.text[:200]}"
                )
            if byte_range is not None and len(resp.content) != (
                byte_range[1] - byte_range[0]
            ):
                # A server legally may ignore Range and return 200 with
                # the full object — that must not masquerade as the slice.
                raise RuntimeError(
                    f"S3 GET {read_io.path} returned "
                    f"{len(resp.content)} bytes, expected "
                    f"{byte_range[1] - byte_range[0]} "
                    f"(status {resp.status_code})"
                )
            return bytearray(resp.content)

        def _get():
            from ._ranged import orchestrated_read

            return orchestrated_read(
                byte_range=read_io.byte_range,
                into=read_io.into,
                chunk_executor=self._chunk_executor,
                stream_into=lambda s, e, v, version=None, cancel=None: (
                    self._stream_get_into(
                        read_io.path, s, e, v, version=version, cancel=cancel
                    )
                ),
                probe_stat=lambda: self._object_stat(read_io.path),
                single_read=_single_read,
                label=f"S3 object {read_io.path}",
            )

        read_io.buf = await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _get
        )

    async def delete(self, path: str) -> None:
        def _delete() -> None:
            resp = self._request("DELETE", self._url(self._key(path)))
            if resp.status_code not in (200, 204, 404):
                raise RuntimeError(
                    f"S3 DELETE {path} failed: {resp.status_code} "
                    f"{resp.text[:200]}"
                )

        await asyncio.get_running_loop().run_in_executor(self._get_executor(), _delete)

    # AWS CopyObject rejects sources over 5 GB; bigger objects are
    # server-side copied part-by-part with UploadPartCopy instead (the
    # reference's aiobotocore path just fails there — incremental snapshots
    # of oversized payloads would re-upload in full).
    _COPY_MAX_BYTES = 5 * 1024 * 1024 * 1024
    _COPY_PART_BYTES = 1024 * 1024 * 1024

    async def copy_from_sibling(self, src_root: str, path: str) -> bool:
        src_bucket, _, src_prefix = src_root.partition("/")
        if src_bucket != self.bucket:
            return False  # cross-bucket copy: fall back to a normal write

        def _copy() -> bool:
            src_key = f"{src_prefix.strip('/')}/{path}" if src_prefix else path
            src_url = f"{self._base}/{urllib.parse.quote(src_key, safe='/')}"
            head = self._request("HEAD", src_url)
            if head.status_code != 200:
                return False
            src_bytes = int(head.headers.get("Content-Length", 0))
            if src_bytes > self._COPY_MAX_BYTES:
                return self._multipart_copy(src_key, path, src_bytes)
            headers = {
                "x-amz-copy-source": urllib.parse.quote(
                    f"/{self.bucket}/{src_key}", safe="/"
                )
            }
            resp = self._request(
                "PUT", self._url(self._key(path)), headers=headers
            )
            if resp.status_code != 200:
                return False
            # CopyObject can return 200 OK with an <Error> body when the
            # copy fails mid-flight (documented AWS behavior): success must
            # carry a CopyObjectResult, or the skipped write would commit a
            # manifest entry whose object doesn't exist.
            return b"CopyObjectResult" in resp.content

        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _copy
        )

    def _multipart_copy(self, src_key: str, path: str, src_bytes: int) -> bool:
        """Server-side copy of a >5 GB object via UploadPartCopy: no byte
        ever traverses this host.  Returns False on any failure (after
        aborting the upload, so no orphaned parts accrue charges) and the
        caller falls back to a normal write."""
        dst_key = self._key(path)
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        try:
            upload_id = self._initiate_multipart(dst_key)
        except RuntimeError:
            return False
        try:
            etags = []
            for number, offset in enumerate(
                range(0, src_bytes, self._COPY_PART_BYTES), start=1
            ):
                end = min(offset + self._COPY_PART_BYTES, src_bytes) - 1
                resp = self._request(
                    "PUT",
                    self._url(
                        dst_key, f"partNumber={number}&uploadId={upload_id}"
                    ),
                    headers={
                        "x-amz-copy-source": urllib.parse.quote(
                            f"/{self.bucket}/{src_key}", safe="/"
                        ),
                        # inclusive both ends, like HTTP Range
                        "x-amz-copy-source-range": f"bytes={offset}-{end}",
                    },
                )
                # UploadPartCopy can 200 with an <Error> body mid-copy, same
                # as CopyObject: require the success element.
                if (
                    resp.status_code != 200
                    or b"CopyPartResult" not in resp.content
                ):
                    raise RuntimeError(
                        f"UploadPartCopy {number} failed: {resp.status_code}"
                    )
                part_tree = ElementTree.fromstring(resp.content)
                etag_el = part_tree.find(f"{ns}ETag")
                if etag_el is None:
                    etag_el = part_tree.find("ETag")
                etags.append((number, etag_el.text if etag_el is not None else ""))
            self._complete_multipart(dst_key, upload_id, etags)
            return True
        except Exception:
            self._abort_multipart(dst_key, upload_id)
            return False

    async def exists(self, path: str) -> bool:
        def _head() -> bool:
            # HEAD: one cheap round-trip instead of downloading the object.
            resp = self._request("HEAD", self._url(self._key(path)))
            if resp.status_code == 200:
                return True
            if resp.status_code == 404:
                return False
            raise RuntimeError(
                f"S3 HEAD {path} failed: {resp.status_code}"
            )

        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _head
        )

    async def list_dir(self, path: str) -> list:
        def _list() -> list:
            prefix = self._key(path).rstrip("/")
            prefix = f"{prefix}/" if prefix else ""
            children = set()
            token = None
            while True:
                query = (
                    "list-type=2&delimiter=%2F&prefix="
                    + urllib.parse.quote(prefix, safe="")
                )
                if token:
                    query += "&continuation-token=" + urllib.parse.quote(
                        token, safe=""
                    )
                resp = self._request("GET", f"{self._base}?{query}")
                if resp.status_code != 200:
                    raise RuntimeError(
                        f"S3 LIST failed: {resp.status_code} {resp.text[:200]}"
                    )
                ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
                tree = ElementTree.fromstring(resp.content)
                for contents in tree.iter(f"{ns}Contents"):
                    children.add(
                        contents.find(f"{ns}Key").text[len(prefix):]
                    )
                for cp in tree.iter(f"{ns}CommonPrefixes"):
                    children.add(
                        cp.find(f"{ns}Prefix").text[len(prefix):].rstrip("/")
                    )
                truncated = tree.find(f"{ns}IsTruncated")
                if truncated is None or truncated.text != "true":
                    break
                token_el = tree.find(f"{ns}NextContinuationToken")
                token = token_el.text if token_el is not None else None
                if token is None:
                    break
            return sorted(c for c in children if c)

        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _list
        )

    async def delete_dir(self, path: str) -> None:
        def _delete_dir() -> None:
            prefix = self._key(path).rstrip("/") + "/"
            token: Optional[str] = None
            while True:
                query = "list-type=2&prefix=" + urllib.parse.quote(prefix, safe="")
                if token:
                    query += "&continuation-token=" + urllib.parse.quote(
                        token, safe=""
                    )
                resp = self._request("GET", f"{self._base}?{query}")
                if resp.status_code != 200:
                    raise RuntimeError(
                        f"S3 LIST failed: {resp.status_code} {resp.text[:200]}"
                    )
                ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
                tree = ElementTree.fromstring(resp.content)
                keys = [c.find(f"{ns}Key").text for c in tree.iter(f"{ns}Contents")]

                def _del_one(key: str) -> None:
                    del_resp = self._request("DELETE", self._url(key))
                    if del_resp.status_code not in (200, 204, 404):
                        raise RuntimeError(
                            f"S3 DELETE {key} failed: {del_resp.status_code}"
                        )

                # Fan the per-key DELETEs across a DEDICATED pool: this
                # function already occupies an I/O-pool thread and blocks on
                # its children, so submitting them to the same pool can
                # starve/deadlock once concurrent blocking ops hold every
                # slot (the same parent/child split fs.py makes for chunk
                # reads).
                futures = [
                    self._get_delete_executor().submit(_del_one, key)
                    for key in keys
                ]
                for fut in futures:
                    fut.result()
                truncated = tree.find(f"{ns}IsTruncated")
                if truncated is None or truncated.text != "true":
                    return
                token_el = tree.find(f"{ns}NextContinuationToken")
                token = token_el.text if token_el is not None else None
                if token is None:
                    return

        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _delete_dir
        )

    async def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown()
            self._executor = None
        self._delete_executor.shutdown()
        self._chunk_executor.shutdown()
