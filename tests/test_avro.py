"""Avro container IO + AvroReader tests: full round-trips over a
Passenger-shaped file written here (the shape of the reference test-data's
Java-written ``PassengerData.avro``: nullable unions, string / boolean /
numeric maps), plus the Java-written snappy file itself where the reference
checkout is on the machine."""

import os

import numpy as np
import pytest

from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.readers import AvroReader, DataReaders, save_avro
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.utils.avro_io import (
    avro_schema_of_records, read_avro, read_avro_schema, write_avro,
)

PASSENGER_AVRO = "/root/reference/test-data/PassengerData.avro"


def _nullable(t):
    return ["null", t]


PASSENGER_SCHEMA = {
    "type": "record", "name": "Passenger",
    "namespace": "com.salesforce.op.test",
    "fields": [
        {"name": "passengerId", "type": "int"},
        {"name": "age", "type": _nullable("int")},
        {"name": "gender", "type": _nullable("string")},
        {"name": "height", "type": _nullable("int")},
        {"name": "weight", "type": _nullable("int")},
        {"name": "description", "type": _nullable("string")},
        {"name": "boarded", "type": _nullable("long")},
        {"name": "recordDate", "type": _nullable("long")},
        {"name": "survived", "type": _nullable("boolean")},
        {"name": "numericMap",
         "type": _nullable({"type": "map", "values": "double"})},
        {"name": "booleanMap",
         "type": _nullable({"type": "map", "values": "boolean"})},
        {"name": "stringMap",
         "type": _nullable({"type": "map", "values": "string"})},
    ],
}


def _passenger_records():
    """Eight records over six passengers (ids 1 and 4 appear twice, at
    different ``recordDate``), with missing ages, descriptions and maps."""
    def rec(pid, age, gender, height, weight, desc, date, survived):
        return {
            "passengerId": pid, "age": age, "gender": gender,
            "height": height, "weight": weight, "description": desc,
            "boarded": 1471046200 + pid, "recordDate": date,
            "survived": survived,
            "numericMap": {gender: float(pid)} if age is not None else None,
            "booleanMap": {gender: bool(survived)} if pid != 3 else {},
            "stringMap": {gender: "string"},
        }
    return [
        rec(1, 32, "Female", 168, 67, None, 1471046100, False),
        rec(1, 33, "Female", 168, 68, "a year on", 1502582100, False),
        rec(2, None, "Male", 180, 78, "", 1471046400, True),
        rec(3, 23, "Female", 172, 85, "this is a description", None, True),
        rec(4, 45, "Male", 175, 0, "stuff", 1471046600, False),
        rec(4, None, "Male", 175, 92, None, 1471046700, True),
        rec(5, 50, "Male", 186, 96, "text text", 1471046800, None),
        rec(6, 19, "Female", 160, 54, None, 1471046900, True),
    ]


@pytest.fixture
def passenger_avro(tmp_path):
    p = str(tmp_path / "PassengerData.avro")
    write_avro(p, PASSENGER_SCHEMA, _passenger_records(), codec="snappy")
    return p


@pytest.mark.skipif(not os.path.exists(PASSENGER_AVRO),
                    reason="the reference checkout's test-data is not on "
                           "this machine")
def test_read_java_written_snappy_file():
    schema, recs = read_avro(PASSENGER_AVRO)
    assert schema["name"] == "Passenger"
    assert len(recs) == 8
    first = recs[0]
    assert first["passengerId"] == 1
    assert first["gender"] == "Female"
    assert first["stringMap"] == {"Female": "string"}
    assert first["booleanMap"] == {"Female": False}


@pytest.mark.parametrize("codec", ["null", "deflate", "snappy"])
def test_round_trip_all_codecs(tmp_path, codec):
    schema, recs = PASSENGER_SCHEMA, _passenger_records()
    p = str(tmp_path / f"rt_{codec}.avro")
    write_avro(p, schema, recs, codec=codec)
    s2, r2 = read_avro(p)
    assert s2 == schema
    assert r2 == recs
    assert read_avro_schema(p) == schema


def test_avro_reader_infers_feature_schema_and_generates_frame(
        passenger_avro):
    reader = AvroReader(passenger_avro, key_col="passengerId")
    sch = reader.schema()
    assert sch["age"] is ft.Integral
    assert sch["gender"] is ft.Text
    assert sch["numericMap"] is ft.RealMap
    assert sch["booleanMap"] is ft.BinaryMap

    age = FeatureBuilder.Integral("age").as_predictor()
    gender = FeatureBuilder.Text("gender").as_predictor()
    frame = reader.generate_frame([age, gender])
    assert frame.n_rows == 8
    assert frame.key[0] == "1"
    # age has some missing values in the dataset
    assert frame["age"].mask.sum() < 8


def test_aggregate_avro_reader(passenger_avro):
    reader = DataReaders.Aggregate.avro(
        passenger_avro, key_fn=lambda r: str(r["passengerId"]),
        time_fn=lambda r: int(r["recordDate"] or 0))
    weight = FeatureBuilder.Integral("weight").as_predictor()
    frame = reader.generate_frame([weight])
    # one row per distinct passengerId
    assert frame.n_rows == len(set(frame.key)) == 6


def test_save_avro_round_trips_frame(tmp_path):
    from transmogrifai_tpu.frame import HostFrame
    frame = HostFrame.from_dict({
        "x": (ft.Real, [1.5, None, 3.0]),
        "label": (ft.Text, ["a", "b", None]),
        "tags": (ft.MultiPickList, [{"p"}, set(), {"q", "r"}]),
    }, key=np.asarray(["r1", "r2", "r3"], dtype=object))
    p = str(tmp_path / "frame.avro")
    save_avro(frame, p)
    schema, recs = read_avro(p)
    assert len(recs) == 3
    by_key = {r["key"]: r for r in recs}
    assert by_key["r1"]["x"] == 1.5
    assert by_key["r2"]["x"] is None
    assert sorted(by_key["r3"]["tags"]) == ["q", "r"]


def test_schema_inference_mixed_numeric():
    recs = [{"a": 1, "b": None}, {"a": 2.5, "b": "s"}]
    sch = avro_schema_of_records(recs)
    types = {f["name"]: f["type"] for f in sch["fields"]}
    assert types["a"] == ["null", "double"]
    assert types["b"] == ["null", "string"]
