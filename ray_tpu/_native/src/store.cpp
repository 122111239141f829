// Native shared-memory object store (plasma equivalent).
//
// Reference: src/ray/object_manager/plasma/ — ObjectStore
// (object_store.cc), PlasmaAllocator over dlmalloc (plasma_allocator.cc,
// dlmalloc.cc), ObjectLifecycleManager + LRU EvictionPolicy
// (object_lifecycle_manager.cc, eviction_policy.cc). Re-designed without
// a store daemon: ONE mmap'd arena file under /dev/shm shared by every
// process; a process-shared robust mutex guards a boundary-tag first-fit
// allocator and an open-addressing object index living inside the arena
// itself (so any process can create/seal/get/release without RPC — the
// fd-passing protocol of plasma's fling.cc is unnecessary when everyone
// maps the same file).
//
// Layout:
//   [Header | index slots | heap ...]
// Heap blocks carry size+prev_size boundary tags for O(1) coalescing.
// Eviction: sealed refcount==0 objects are reclaimed in LRU order when
// an allocation fails (eviction_policy.cc semantics).
//
// All cross-process references are OFFSETS from the arena base, never
// pointers. C ABI at the bottom; Python binds with ctypes and reads
// object payloads zero-copy through its own mmap of the same file.

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <cstdio>
#include <fcntl.h>
#include <pthread.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr uint64_t kMagic = 0x52545053544f5245ULL;  // "RTPSTORE"
constexpr uint32_t kIdLen = 16;
constexpr uint32_t kSlots = 1 << 15;        // index capacity (open addr)
constexpr uint64_t kAlign = 64;             // block alignment (cacheline)

enum SlotState : uint32_t {
  SLOT_FREE = 0,
  SLOT_TOMB = 1,
  SLOT_CREATED = 2,   // allocated, being written
  SLOT_SEALED = 3,    // immutable, readable
};

struct Slot {
  uint8_t id[kIdLen];
  uint32_t state;
  int32_t refcount;
  uint64_t offset;     // payload offset from arena base
  uint64_t size;       // payload size
  uint64_t lru_tick;   // last-touch tick for eviction order
};

struct BlockHeader {
  uint64_t size;       // payload capacity of this block (excl. header)
  uint64_t prev_size;  // size of previous block's payload (0 if first)
  uint32_t used;       // 1 = allocated
  uint32_t pad;
};

struct Header {
  uint64_t magic;
  uint64_t capacity;       // total file size
  uint64_t heap_off;       // offset of first block header
  uint64_t heap_end;       // end offset of heap
  uint64_t used_bytes;     // allocated payload bytes
  uint64_t num_objects;
  uint64_t lru_clock;
  uint64_t evictions;
  pthread_mutex_t lock;    // process-shared robust mutex
  Slot slots[kSlots];
};

struct Store {
  int fd;
  uint8_t* base;
  uint64_t size;
  Header* hdr;
};

inline BlockHeader* block_at(Store* s, uint64_t off) {
  return reinterpret_cast<BlockHeader*>(s->base + off);
}

inline uint64_t payload_off(uint64_t block_off) {
  return block_off + sizeof(BlockHeader);
}

inline uint64_t next_block_off(uint64_t block_off, BlockHeader* b) {
  return block_off + sizeof(BlockHeader) + b->size;
}

uint64_t align_up(uint64_t v, uint64_t a) { return (v + a - 1) & ~(a - 1); }

// FNV-1a over the id for index hashing.
uint64_t hash_id(const uint8_t* id) {
  uint64_t h = 1469598103934665603ULL;
  for (uint32_t i = 0; i < kIdLen; i++) { h ^= id[i]; h *= 1099511628211ULL; }
  return h;
}

struct Guard {
  pthread_mutex_t* m;
  explicit Guard(pthread_mutex_t* mu) : m(mu) {
    int rc = pthread_mutex_lock(m);
    if (rc == EOWNERDEAD) pthread_mutex_consistent(m);  // robust recovery
  }
  ~Guard() { pthread_mutex_unlock(m); }
};

Slot* find_slot(Header* h, const uint8_t* id) {
  uint64_t idx = hash_id(id) & (kSlots - 1);
  for (uint32_t probe = 0; probe < kSlots; probe++) {
    Slot* s = &h->slots[(idx + probe) & (kSlots - 1)];
    if (s->state == SLOT_FREE) return nullptr;
    if (s->state != SLOT_TOMB && memcmp(s->id, id, kIdLen) == 0) return s;
  }
  return nullptr;
}

Slot* insert_slot(Header* h, const uint8_t* id) {
  uint64_t idx = hash_id(id) & (kSlots - 1);
  Slot* tomb = nullptr;
  for (uint32_t probe = 0; probe < kSlots; probe++) {
    Slot* s = &h->slots[(idx + probe) & (kSlots - 1)];
    if (s->state == SLOT_FREE) {
      Slot* target = tomb ? tomb : s;
      memcpy(target->id, id, kIdLen);
      return target;
    }
    if (s->state == SLOT_TOMB) { if (!tomb) tomb = s; continue; }
    if (memcmp(s->id, id, kIdLen) == 0) return nullptr;  // exists
  }
  if (tomb) { memcpy(tomb->id, id, kIdLen); return tomb; }
  return nullptr;  // table full
}

// -- allocator (boundary-tag first fit, reference: dlmalloc.cc role) ------
int64_t alloc_block(Store* st, uint64_t want) {
  want = align_up(want < kAlign ? kAlign : want, kAlign);
  Header* h = st->hdr;
  uint64_t off = h->heap_off;
  while (off + sizeof(BlockHeader) <= h->heap_end) {
    BlockHeader* b = block_at(st, off);
    if (!b->used && b->size >= want) {
      // split when the remainder can hold a minimal block
      if (b->size >= want + sizeof(BlockHeader) + kAlign) {
        uint64_t rest = b->size - want - sizeof(BlockHeader);
        b->size = want;
        uint64_t noff = next_block_off(off, b);
        BlockHeader* nb = block_at(st, noff);
        nb->size = rest;
        nb->prev_size = want;
        nb->used = 0;
        uint64_t after = next_block_off(noff, nb);
        if (after + sizeof(BlockHeader) <= h->heap_end)
          block_at(st, after)->prev_size = rest;
      }
      b->used = 1;
      h->used_bytes += b->size;
      return static_cast<int64_t>(payload_off(off));
    }
    off = next_block_off(off, b);
  }
  return -1;
}

void free_block(Store* st, uint64_t pay_off) {
  Header* h = st->hdr;
  uint64_t off = pay_off - sizeof(BlockHeader);
  BlockHeader* b = block_at(st, off);
  b->used = 0;
  h->used_bytes -= b->size;
  // coalesce with next
  uint64_t noff = next_block_off(off, b);
  if (noff + sizeof(BlockHeader) <= h->heap_end) {
    BlockHeader* nb = block_at(st, noff);
    if (!nb->used) {
      b->size += sizeof(BlockHeader) + nb->size;
      uint64_t after = next_block_off(off, b);
      if (after + sizeof(BlockHeader) <= h->heap_end)
        block_at(st, after)->prev_size = b->size;
    }
  }
  // coalesce with prev
  if (b->prev_size != 0) {
    uint64_t poff = off - sizeof(BlockHeader) - b->prev_size;
    BlockHeader* pb = block_at(st, poff);
    if (!pb->used) {
      pb->size += sizeof(BlockHeader) + b->size;
      uint64_t after = next_block_off(poff, pb);
      if (after + sizeof(BlockHeader) <= h->heap_end)
        block_at(st, after)->prev_size = pb->size;
    }
  }
}

// Evict one LRU sealed, unreferenced object. Caller holds the lock.
bool evict_one(Store* st) {
  Header* h = st->hdr;
  Slot* victim = nullptr;
  for (uint32_t i = 0; i < kSlots; i++) {
    Slot* s = &h->slots[i];
    if (s->state == SLOT_SEALED && s->refcount <= 0) {
      if (!victim || s->lru_tick < victim->lru_tick) victim = s;
    }
  }
  if (!victim) return false;
  free_block(st, victim->offset);
  victim->state = SLOT_TOMB;
  h->num_objects--;
  h->evictions++;
  return true;
}

// Why the last rt_store_create/rt_store_open of this thread returned
// null: the step that failed and its errno, for the Python side's OSError.
thread_local const char* g_fail_step = "";
thread_local int g_fail_errno = 0;

// Failure exit of create/open: record step + errno, drop the fd, and
// remove a half-made arena file so it cannot be mistaken for a peer's.
Store* fail(const char* step, int fd, const char* unlink_path) {
  g_fail_errno = errno;
  g_fail_step = step;
  if (fd >= 0) close(fd);
  if (unlink_path) unlink(unlink_path);
  return nullptr;
}

}  // namespace

extern "C" {

Store* rt_store_create(const char* path, uint64_t capacity) {
  if (capacity < sizeof(Header) + (1 << 20)) capacity = sizeof(Header) + (1 << 20);
  int fd = open(path, O_CREAT | O_RDWR | O_EXCL, 0600);
  if (fd < 0) return fail("open", -1, nullptr);
  if (ftruncate(fd, static_cast<off_t>(capacity)) != 0) return fail("ftruncate", fd, path);
  void* base = mmap(nullptr, capacity, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (base == MAP_FAILED) return fail("mmap", fd, path);
  Store* st = new Store{fd, static_cast<uint8_t*>(base), capacity, nullptr};
  Header* h = reinterpret_cast<Header*>(base);
  memset(h, 0, sizeof(Header));
  h->capacity = capacity;
  h->heap_off = align_up(sizeof(Header), kAlign);
  h->heap_end = capacity;
  pthread_mutexattr_t attr;
  pthread_mutexattr_init(&attr);
  pthread_mutexattr_setpshared(&attr, PTHREAD_PROCESS_SHARED);
  pthread_mutexattr_setrobust(&attr, PTHREAD_MUTEX_ROBUST);
  pthread_mutex_init(&h->lock, &attr);
  pthread_mutexattr_destroy(&attr);
  BlockHeader* first = block_at(st, h->heap_off);
  first->size = h->heap_end - h->heap_off - sizeof(BlockHeader);
  first->prev_size = 0;
  first->used = 0;
  h->magic = kMagic;  // publish last
  st->hdr = h;
  return st;
}

Store* rt_store_open(const char* path) {
  int fd = open(path, O_RDWR);
  if (fd < 0) return fail("open", -1, nullptr);
  struct stat sb;
  if (fstat(fd, &sb) != 0) return fail("fstat", fd, nullptr);
  void* base = mmap(nullptr, sb.st_size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (base == MAP_FAILED) return fail("mmap", fd, nullptr);
  Header* h = reinterpret_cast<Header*>(base);
  if (h->magic != kMagic) {
    munmap(base, sb.st_size);
    errno = EILSEQ;  // a file, but not a published arena
    return fail("magic check", fd, nullptr);
  }
  return new Store{fd, static_cast<uint8_t*>(base),
                   static_cast<uint64_t>(sb.st_size), h};
}

// Reserve space for an object; returns payload offset or -1.
// (plasma Create; two-phase create/seal like plasma's CreateObject.)
int64_t rt_store_create_obj(Store* st, const uint8_t* id, uint64_t size) {
  Guard g(&st->hdr->lock);
  if (find_slot(st->hdr, id)) return -2;  // duplicate
  int64_t off = alloc_block(st, size);
  while (off < 0) {
    if (!evict_one(st)) return -1;       // full, nothing evictable
    off = alloc_block(st, size);
  }
  Slot* s = insert_slot(st->hdr, id);
  if (!s) { free_block(st, off); return -3; }  // index full
  s->state = SLOT_CREATED;
  s->refcount = 1;                        // creator holds a ref
  s->offset = static_cast<uint64_t>(off);
  s->size = size;
  s->lru_tick = ++st->hdr->lru_clock;
  st->hdr->num_objects++;
  return off;
}

int rt_store_seal(Store* st, const uint8_t* id) {
  Guard g(&st->hdr->lock);
  Slot* s = find_slot(st->hdr, id);
  if (!s || s->state != SLOT_CREATED) return -1;
  s->state = SLOT_SEALED;
  return 0;
}

// One-shot put = create + memcpy + seal.
int64_t rt_store_put(Store* st, const uint8_t* id, const void* data,
                     uint64_t size) {
  int64_t off = rt_store_create_obj(st, id, size);
  if (off < 0) return off;
  memcpy(st->base + off, data, size);
  rt_store_seal(st, id);
  return off;
}

// Lookup: fills offset/size, increfs (pin for reading). Returns 0, or -1.
int rt_store_get(Store* st, const uint8_t* id, uint64_t* off_out,
                 uint64_t* size_out) {
  Guard g(&st->hdr->lock);
  Slot* s = find_slot(st->hdr, id);
  if (!s || s->state != SLOT_SEALED) return -1;
  s->refcount++;
  s->lru_tick = ++st->hdr->lru_clock;
  *off_out = s->offset;
  *size_out = s->size;
  return 0;
}

int rt_store_contains(Store* st, const uint8_t* id) {
  Guard g(&st->hdr->lock);
  Slot* s = find_slot(st->hdr, id);
  return (s && s->state == SLOT_SEALED) ? 1 : 0;
}

// Drop a pin (reader done / creator done). Objects with refcount 0 stay
// sealed until evicted or deleted (plasma Release semantics).
int rt_store_release(Store* st, const uint8_t* id) {
  Guard g(&st->hdr->lock);
  Slot* s = find_slot(st->hdr, id);
  if (!s || s->state < SLOT_CREATED) return -1;
  if (s->refcount > 0) s->refcount--;
  return 0;
}

// Owner-driven delete (refcount went to 0 cluster-wide).
int rt_store_delete(Store* st, const uint8_t* id) {
  Guard g(&st->hdr->lock);
  Slot* s = find_slot(st->hdr, id);
  if (!s || s->state < SLOT_CREATED) return -1;
  if (s->refcount > 0) return -2;  // pinned by a reader
  free_block(st, s->offset);
  s->state = SLOT_TOMB;
  st->hdr->num_objects--;
  return 0;
}

uint64_t rt_store_used(Store* st) { return st->hdr->used_bytes; }
uint64_t rt_store_capacity(Store* st) { return st->hdr->capacity; }
uint64_t rt_store_num_objects(Store* st) { return st->hdr->num_objects; }
uint64_t rt_store_evictions(Store* st) { return st->hdr->evictions; }

void rt_store_close(Store* st) {
  munmap(st->base, st->size);
  close(st->fd);
  delete st;
}

int rt_store_unlink(const char* path) { return unlink(path); }

const char* rt_store_fail_step() { return g_fail_step; }
int rt_store_fail_errno() { return g_fail_errno; }

uint8_t* rt_store_base_ptr(Store* st) { return st->base; }

}  // extern "C"
