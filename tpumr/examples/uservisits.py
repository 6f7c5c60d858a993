"""The UserVisits aggregation: ``SELECT sourceIP, SUM(adRevenue) FROM
UserVisits GROUP BY sourceIP``.

≈ the Aggregation task of Pavlo et al., *A Comparison of Approaches to
Large-Scale Data Analysis* (SIGMOD 2009, section 4.3.3), HiBench's
``aggregation``: a scan of delimited text rows (nine columns, ``sourceIP``
first, ``adRevenue`` fourth), a shuffle on a key of high cardinality, a
sum. The map parses a whole split at a time and emits dense fixed-width
records (key: ``sourceIP`` padded with zero bytes to 16; value:
``adRevenue`` as a little-endian float32). With ``--device-shuffle`` the
job NAMES its reducer as a kernel (``segment-sum-f32``): the device sorts
the rows, sums each group where it sorted it, and only the groups come
back. Without it the same sums are made by a reducer class behind the
host shuffle. There is no combiner: the dense map output path has none.
"""

from __future__ import annotations

import argparse
import struct

import numpy as np

from tpumr.core import tracing
from tpumr.examples import register
from tpumr.mapred.api import Mapper, RawComparator, Reducer
from tpumr.mapred.input_formats import BytesTextInputFormat
from tpumr.mapred.job_client import run_job
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.output_formats import SequenceFileOutputFormat
from tpumr.mapred.total_order import (TotalOrderPartitioner, sample_input,
                                      write_partition_file)

KEY_LEN = 16            # sourceIP VARCHAR(16)
VALUE_LEN = 4           # adRevenue FLOAT
COLUMNS = 9
DELIMITER = b"|"
REVENUE_COLUMN = 3
#: the most digits whose whole number a float64 holds exactly
_EXACT_DIGITS = 15


def record_of(line: bytes) -> "tuple[bytes, bytes]":
    """One row's (key, value), the slow way: the reference of
    ``parse_rows`` and the per-record map."""
    fields = line.split(DELIMITER)
    if len(fields) != COLUMNS:
        raise ValueError(f"a UserVisits row has {COLUMNS} columns, got "
                         f"{len(fields)}: {line[:80]!r}")
    if not 0 < len(fields[0]) <= KEY_LEN:
        raise ValueError(f"sourceIP is VARCHAR({KEY_LEN}): {fields[0]!r}")
    return (fields[0].ljust(KEY_LEN, b"\0"),
            struct.pack("<f", float(fields[REVENUE_COLUMN])))


def parse_rows(data: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """``[n, 20]`` uint8 records of ``n`` text rows (``data`` their bytes
    end to end without newlines, row ``i`` at ``offsets[i]:offsets[i +
    1]``), all rows at once: the delimiters found in one pass, the key
    gathered to 16 bytes, the decimal digits of ``adRevenue`` folded to a
    whole number and divided by its power of ten in float64 (exact for up
    to 15 digits, so the float32 is what ``float()`` gives). A row that
    does not fit (an exponent, more digits) is parsed by ``record_of``."""
    n = offsets.shape[0] - 1
    out = np.zeros((n, KEY_LEN + VALUE_LEN), np.uint8)
    if n == 0:
        return out
    offsets = offsets.astype(np.int64)
    starts = offsets[:-1]
    delims = np.flatnonzero(data == DELIMITER[0])
    if delims.size != (COLUMNS - 1) * n:
        raise ValueError(f"{n} UserVisits rows hold {delims.size} "
                         f"delimiters, not {COLUMNS - 1} each")
    delims = delims.reshape(n, COLUMNS - 1)
    if (delims[:, 0] < starts).any() or (delims[:, -1] >= offsets[1:]).any():
        raise ValueError(f"a UserVisits row has not {COLUMNS} columns")
    key_len = delims[:, 0] - starts
    if key_len.min() < 1 or key_len.max() > KEY_LEN:
        raise ValueError(f"sourceIP is VARCHAR({KEY_LEN})")
    at = np.arange(KEY_LEN)
    last = data.shape[0] - 1
    out[:, :KEY_LEN] = np.where(
        at < key_len[:, None],
        data[np.minimum(starts[:, None] + at, last)], 0)

    lo = delims[:, REVENUE_COLUMN - 1] + 1
    width = delims[:, REVENUE_COLUMN] - lo
    whole = np.zeros(n, np.int64)
    digits = np.zeros(n, np.int64)
    after_dot = np.zeros(n, np.int64)
    seen_dot = np.zeros(n, bool)
    plain = width > 0
    for col in range(int(width.max())):
        inside = col < width
        c = data[np.minimum(lo + col, last)]
        dot = inside & (c == ord("."))
        digit = inside & (c >= ord("0")) & (c <= ord("9"))
        plain &= ~inside | digit | (dot & ~seen_dot)
        whole = np.where(digit, whole * 10 + (c - ord("0")), whole)
        digits += digit
        after_dot += digit & seen_dot
        seen_dot |= dot
    plain &= (digits > 0) & (digits <= _EXACT_DIGITS)
    revenue = (whole / 10.0 ** after_dot).astype("<f4")
    for i in np.flatnonzero(~plain):
        revenue[i] = float(bytes(data[lo[i]:lo[i] + width[i]]))
    out[:, KEY_LEN:] = revenue.view(np.uint8).reshape(n, VALUE_LEN)
    return out


class UserVisitsAggMapper(Mapper):
    """(sourceIP padded to 16 bytes, adRevenue as float32) a row."""

    def map(self, key, value, output, reporter):
        line = value if isinstance(value, (bytes, bytearray)) \
            else str(value).encode()
        if line:
            output.collect(*record_of(bytes(line)))

    def map_record_batch(self, batch, output, reporter) -> None:
        """The whole split at once (map_task._host_batch_fast_path): a
        per-record map cannot parse ten million rows in a job's time."""
        with tracing.span("map:parse", rows=batch.num_records,
                          bytes=int(batch.value_data.nbytes)):
            offsets = batch.value_offsets
            full = np.diff(offsets) > 0     # a blank line is no row
            rows = parse_rows(batch.value_data, np.concatenate(
                [offsets[:-1][full], offsets[-1:]]))
        output.collect_fixed_rows(rows, KEY_LEN)


class RevenueSumReducer(Reducer):
    """The host path's reducer: a group's float32 values added one after
    another, in float32."""

    def reduce(self, key, values, output, reporter):
        total = np.float32(0.0)
        for v in values:
            total = np.float32(total + np.frombuffer(v, "<f4")[0])
        output.collect(key, struct.pack("<f", total))


def make_uservisits_agg_conf(input_path: str, output_path: str,
                             reduces: int,
                             device_shuffle: bool = False) -> JobConf:
    """The aggregation's job conf (shared with tests): sampled range
    partitioning as terasort has it, so the part files are in total key
    order; behind the device shuffle the reducer is the
    ``segment-sum-f32`` kernel."""
    conf = JobConf()
    conf.set_job_name("uservisits-agg")
    conf.set_input_paths(input_path)
    conf.set_output_path(output_path)
    conf.set_input_format(BytesTextInputFormat)
    conf.set_mapper_class(UserVisitsAggMapper)
    conf.set_output_format(SequenceFileOutputFormat)
    conf.set_output_key_comparator_class(RawComparator)
    conf.set_num_reduce_tasks(reduces)
    samples = sample_input(conf, num_samples=1000,
                           key_of=lambda _k, line: record_of(line)[0])
    write_partition_file(conf, output_path.rstrip("/") + ".partitions",
                         samples, reduces)
    conf.set_partitioner_class(TotalOrderPartitioner)
    if device_shuffle:
        conf.set_device_shuffle(KEY_LEN, VALUE_LEN)
        conf.set_reduce_kernel("segment-sum-f32")
    else:
        conf.set_reducer_class(RevenueSumReducer)
    return conf


@register("uservisits-agg",
          "SUM(adRevenue) GROUP BY sourceIP over UserVisits text rows")
def uservisits_agg(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(prog="tpumr examples uservisits-agg")
    ap.add_argument("input")
    ap.add_argument("output")
    ap.add_argument("-r", "--reduces", type=int, default=2)
    ap.add_argument("--device-shuffle", action="store_true",
                    help="sort AND sum on the device (the reducer is the "
                         "segment-sum-f32 kernel); only groups come back")
    args = ap.parse_args(argv)
    conf = make_uservisits_agg_conf(args.input, args.output, args.reduces,
                                    device_shuffle=args.device_shuffle)
    return 0 if run_job(conf).successful else 1
