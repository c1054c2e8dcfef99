# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: table_service.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x13table_service.proto\x12\x08\x62rpc_tpu\"\x1f\n\x10TableReadRequest\x12\x0b\n\x03key\x18\x01 \x01(\x04\"#\n\x11TableReadResponse\x12\x0e\n\x06record\x18\x01 \x01(\x0c\"?\n\x12TableUpdateRequest\x12\x0b\n\x03key\x18\x01 \x01(\x04\x12\r\n\x05\x66ield\x18\x02 \x01(\r\x12\r\n\x05value\x18\x03 \x01(\x0c\"!\n\x13TableUpdateResponse\x12\n\n\x02ok\x18\x01 \x01(\x08\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'table_service_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TABLEREADREQUEST._serialized_start=33
  _TABLEREADREQUEST._serialized_end=64
  _TABLEREADRESPONSE._serialized_start=66
  _TABLEREADRESPONSE._serialized_end=101
  _TABLEUPDATEREQUEST._serialized_start=103
  _TABLEUPDATEREQUEST._serialized_end=166
  _TABLEUPDATERESPONSE._serialized_start=168
  _TABLEUPDATERESPONSE._serialized_end=201
# @@protoc_insertion_point(module_scope)
